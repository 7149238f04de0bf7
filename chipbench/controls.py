"""Controls and planted faults: the timed path broken on purpose, so that
``correct`` is seen to come out false.

    python3 chipbench/controls.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]
        [--path control|answer_altered|half_batch|state_unchanged]

Each seed is one whole run of the cell (set-up, window, reference
check) in this one process, with the timed path replaced underneath:

``control``
    the plain reference put in the program's place, with one guarantee
    of the configuration broken: RSA answers are left in Montgomery form
    (``x * 2**(32 * limbs) mod n``: the exit conversion skipped);
    products drop the carries between limbs (each limb of the product is
    its column sum mod 2**32).
``answer_altered``
    one bit of one answer flipped where it is produced.
``half_batch``
    the second half of each batch's real lanes left without an answer
    (zeros).
``state_unchanged``
    the step hands back its input unchanged.

The benchmark's own runs never do this.
``chipbench/tests/test_controls.py`` drives the same paths at a size a
test run holds.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import reference  # noqa: E402


def _serve_lanes(execute, reqs):
    """The (slots, limbs) block the engine expects, lanes left zero."""
    slots = execute.__self__.cfg.slots
    return np.zeros((slots, -(-reqs[0].key.bits // 32)), np.uint32)


def serve_control(execute):
    def run(bkey, reqs):
        out = _serve_lanes(execute, reqs)
        r_mont = 1 << (32 * out.shape[1])
        for i, r in enumerate(reqs):
            k = r.key
            key = {"n": k.n, "e": k.e, "d": k.d, "p": k.p, "q": k.q}
            v = reference.to_ints(np.asarray(r.value)[None])[0]
            s = (reference.rsa_private(v, key) if bkey[0] == "rsa_sign"
                 else pow(v, k.e, k.n))
            out[i] = reference.to_limbs([s * r_mont % k.n], out.shape[1])[0]
        return out
    return run


def serve_fault(kind):
    def wrap(execute):
        def run(bkey, reqs):
            if kind == "state_unchanged":
                out = _serve_lanes(execute, reqs)
                for i, r in enumerate(reqs):
                    out[i] = r.value
                return out
            out = np.array(execute(bkey, reqs))
            if kind == "answer_altered":
                out[0, 0] ^= 1
            elif kind == "half_batch":
                out[len(reqs) // 2:len(reqs)] = 0
            return out
        return run
    return wrap


def carryless_product(a, b) -> np.ndarray:
    """(N, m) x (N, m) limbs -> (N, 2m): each limb the column sum of the
    limb products mod 2**32, the carries into the next limb dropped."""
    a = np.asarray(a, np.uint64)
    b = np.asarray(b, np.uint64)
    n, m = a.shape
    out = np.zeros((n, 2 * m), np.uint64)
    mask = np.uint64(0xFFFFFFFF)
    for i in range(m):
        out[:, i:i + m] += (a[:, i:i + 1] * b) & mask
        out[:, i:i + m] &= mask
    return out.astype(np.uint32)


def arith_control(fn):
    def run(a, b):
        return carryless_product(np.asarray(a), np.asarray(b))
    return run


def arith_fault(kind):
    def wrap(fn):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def broken(out, a):
            if kind == "answer_altered":
                return out.at[0, 0].set(out[0, 0] ^ jnp.uint32(1))
            if kind == "half_batch":
                rows = jnp.arange(out.shape[0])[:, None]
                return jnp.where(rows < out.shape[0] // 2, out, 0)
            return jnp.concatenate([a, jnp.zeros_like(a)], axis=-1)

        def run(a, b):
            return broken(fn(a, b), a)
        return run
    return wrap


PATHS = ("control", "answer_altered", "half_batch", "state_unchanged")


def wrapper(driver: str, path: str):
    if path not in PATHS:
        raise ValueError(f"unknown path {path!r}; choose from {PATHS}")
    if driver == "serve_open":
        return serve_control if path == "control" else serve_fault(path)
    if driver == "arith_closed":
        return arith_control if path == "control" else arith_fault(path)
    raise ValueError(f"no controls for driver {driver!r}")


def main(argv=None) -> int:
    from chipbench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--path", default="control", choices=PATHS)
    args = ap.parse_args(argv)
    spec, devs = run.chip_setup(args.workload)
    wrap = wrapper(spec["driver"], args.path)
    for seed in args.seeds:
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           devs=devs, wrap=wrap)
        print(json.dumps({"workload": args.workload, "path": args.path,
                          "seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
