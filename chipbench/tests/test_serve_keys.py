"""The many-key serving driver (``drivers/serve_keys.py``) on the CPU:
its stream names one key a position, its check judges each answer under
its own request's key, its metric reads the engine's counter, an engine
that buckets by modulus stops it in set-up, and a tiny cell of it runs
end to end and is correct.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import REPO, SECONDS, SEED, make_tiny_root

CB = REPO / "chipbench"
TINY = {"key_bits": 256, "keys": 16}
CELL = "rsa256-zipf16-test"


@pytest.fixture(scope="module")
def driver():
    from chipbench import run

    return run.load(CB / "drivers" / "serve_keys.py")


@pytest.fixture(scope="module")
def config():
    conf = json.loads((CB / "configs" / "rsa2048-tls13-sni64.json")
                      .read_text())
    conf.update(TINY)
    return conf


@pytest.fixture(scope="module")
def stream(driver, config):
    return driver.Stream(config, "rsa_sign", SEED)


def test_keys_come_from_the_seed_and_differ(driver, stream):
    assert len({k["n"] for k in stream.keys}) == TINY["keys"]
    assert all(k["n"].bit_length() == TINY["key_bits"] for k in stream.keys)
    again = driver.make_keys(TINY["key_bits"], 65537, 2, SEED)
    assert again == stream.keys[:2]
    other = driver.make_keys(TINY["key_bits"], 65537, 2, SEED + 1)
    assert {k["n"] for k in other}.isdisjoint(k["n"] for k in stream.keys)


@pytest.mark.parametrize("salt", ["window", "warm", "capacity"])
def test_values_and_requests_agree_on_keys(stream, salt):
    values = stream.values(64, salt)
    for i, v in enumerate(values):
        key = stream.keys[stream.key_of(i)]
        for rid in (i, -1 - i):        # window; warm-up and capacity probe
            req = stream.request(rid, None)
            assert req.key.n == key["n"] and req.key.d == key["d"]
        assert 1 <= v < key["n"]


def test_key_draws_follow_the_zipf_weights(stream):
    draws = [stream.key_of(i) for i in range(8192)]
    counts = np.bincount(draws, minlength=TINY["keys"])
    # rank 1 carries 1 / H_16 = 29.6% of the draws, rank 2 half that
    assert counts[0] / len(draws) == pytest.approx(0.296, abs=0.02)
    assert counts[1] / counts[0] == pytest.approx(0.5, abs=0.08)
    assert (counts > 0).all()


def _record(stream, n=24):
    from chipbench import reference

    values = stream.values(n, "window")
    sigs = [reference.rsa_private(v, stream.keys[stream.key_of(i)])
            for i, v in enumerate(values)]
    limbs = reference.to_limbs(sigs, stream.nlimbs)
    return {"values": values, "results": list(limbs), "lost": 0}, sigs


def test_check_judges_each_answer_under_its_own_key(driver, stream):
    from chipbench import reference

    record, sigs = _record(stream)
    assert driver.check(stream, record) == {"wrong_answers": (0, 0),
                                            "lost_requests": (0, 0)}
    # answer 3 signed under another key of the set
    i = 3
    k = stream.key_of(i)
    other = stream.keys[(k + 1) % TINY["keys"]]
    bad = dict(record, results=list(record["results"]))
    s = reference.rsa_private(record["values"][i] % other["n"], other)
    bad["results"][i] = reference.to_limbs([s], stream.nlimbs)[0]
    assert driver.check(stream, bad)["wrong_answers"] == (1, 0)
    # answers 5 and 6 left in Montgomery form, x * 2**(32 * limbs) mod n
    for j in (5, 6):
        n = stream.keys[stream.key_of(j)]["n"]
        mont = sigs[j] * (1 << 32 * stream.nlimbs) % n
        bad["results"][j] = reference.to_limbs([mont], stream.nlimbs)[0]
    assert driver.check(stream, bad)["wrong_answers"] == (3, 0)
    # an answer never given counts as lost, not wrong
    lost = dict(record, results=[None] + record["results"][1:], lost=1)
    assert driver.check(stream, lost) == {"wrong_answers": (0, 0),
                                          "lost_requests": (1, 0)}


def test_keys_per_batch_reads_the_engine_counter():
    from chipbench import run

    metric = [{"name": "keys_per_batch", "unit": "keys"}]
    record = {"kind": "serve", "engine": {"batches": 4, "batch_keys": 22}}
    got = run.metric_values(metric, record, REPO)
    assert got["keys_per_batch"]["value"] == pytest.approx(5.5)
    # a program without the counter has nothing to read
    with pytest.raises(run.BenchError, match="nothing to read"):
        run.metric_values(metric, {"kind": "serve",
                                   "engine": {"batches": 4}}, REPO)


def test_engine_bucketing_by_modulus_stops_setup(driver, config,
                                                 monkeypatch):
    from repro.serve import bignum_engine as BE

    def by_modulus(self, req):
        return (req.op, req.key.bits, None, req.key.n)

    monkeypatch.setattr(BE.BignumEngine, "bucket_key", by_modulus)
    monkeypatch.setattr(BE.BignumEngine, "warm", None)   # never reached
    with pytest.raises(SystemExit, match="two buckets"):
        driver.setup(config, {"op": "rsa_sign", "rate_per_s": 40.0}, SEED)


@pytest.fixture(scope="module")
def keys_root(tmp_path_factory, config):
    """The tiny harness tree plus a 16-key, 256-bit copy of the cell."""
    root = make_tiny_root(tmp_path_factory.mktemp("keys"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    conf = dict(config, name="rsa256-sni16-test", reduced=sorted(TINY))
    (root / "chipbench" / "configs" / "rsa256-sni16-test.json").write_text(
        json.dumps(conf))
    bench["configs"].append({
        "name": conf["name"], "source": conf["source"],
        "file": "chipbench/configs/rsa256-sni16-test.json",
        "reduced": conf["reduced"], "why": "tiny CPU test size"})
    (root / "chipbench" / "workloads" / f"{CELL}.json").write_text(
        json.dumps({"config": conf["name"], "driver": "serve_keys",
                    "traffic": {"op": "rsa_sign", "rate_per_s": 40.0},
                    "trace_seconds": SECONDS}))
    bench["workloads"].append({"name": CELL, "config": conf["name"],
                               "traffic": CELL, "chips": 1,
                               "why": "tiny CPU test size"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "rsa2048-sign-zipf64" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def test_tiny_keys_cell_runs_and_is_correct(keys_root):
    from chipbench import run

    res = run.run_cell(CELL, SEED, SECONDS, False, root=keys_root)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "p50_ms", "p99_ms"}
