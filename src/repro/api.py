"""Public bignum facade: the ONE front door for the paper's arithmetic.

Every operation here takes and returns **32-bit limb arrays** (uint32,
little-endian, limb axis last, leading axes are batch lanes -- the
GMP-facing radix of ``core/limbs.py``) and follows one kwarg
convention:

  * ``method=``  picks a multiply/divide pipeline implementation
    ("auto" dispatches by size and batch; see core/mul.select_method,
    core/div.select_div_method),
  * ``backend=`` picks a modular-arithmetic device backend (None
    auto-dispatches; see core/modular.select_modexp_backend).

This replaces the per-module scatter of entry points (mul_limbs32 /
divmod_limbs32 / mod_exp-on-digit-arrays / rsa.sign...) for callers
that just want arithmetic: the serving engine
(serve/bignum_engine.py), the examples, and downstream users all go
through here.  The digit-radix internals stay importable for kernels
and tests.

Configuration
-------------
``configure(...)`` is the supported way to override dispatch:

    repro.api.configure(mul_method="ntt")          # process-wide
    with repro.api.configure(modexp_backend="jnp"):  # scoped
        ...

The legacy ``REPRO_MUL_BACKEND`` / ``REPRO_DIV_BACKEND`` /
``REPRO_MODEXP_BACKEND`` / ``REPRO_AUTOTUNE`` environment variables
keep working as deprecated aliases (one DeprecationWarning per process
each) at lower precedence; see repro/config.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import config as _config
from repro.core import div as _div
from repro.core import limbs as _L
from repro.core import modular as _M
from repro.core import mul as _mul
from repro.core import rsa as _rsa

U32 = jnp.uint32
DIGIT_BITS = 16

# re-exported names that already have the right shape/contract
mod_setup = _M.mod_setup
exp_bits_msb = _M.exp_bits_msb
generate_key = _rsa.generate_key
digest_int = _rsa.digest_int
RSAKey = _rsa.RSAKey
KeyLanes = _rsa.KeyLanes
key_lanes = _rsa.key_lanes

__all__ = [
    "mul", "divmod", "mod_exp", "rsa_sign", "rsa_verify", "rsa_decrypt",
    "to_decimal", "configure", "cache_stats", "metrics", "dispatch_report",
    "to_limbs", "from_limbs",
    "mod_setup", "exp_bits_msb", "generate_key", "digest_int", "RSAKey",
    "KeyLanes", "key_lanes",
]


# ---------------------------------------------------------------------------
# host-side conversions
# ---------------------------------------------------------------------------

def to_limbs(values, nbits: int) -> np.ndarray:
    """Python int(s) -> uint32 limb array sized for ``nbits``.

    A single int gives (m,); a sequence gives (len, m) with
    m = ceil(nbits / 32).  Values must be >= 0 and < 2**nbits (the
    declared width, not the rounded-up limb width).  Bad inputs raise
    ValueError naming the offending argument here at the facade, not as
    shape errors deep in the limb layer."""
    import operator

    if not isinstance(nbits, int) or isinstance(nbits, bool) or nbits <= 0:
        raise ValueError(
            f"to_limbs: nbits must be a positive int, got {nbits!r}")
    m = -(-nbits // 32)
    single = isinstance(values, int) and not isinstance(values, bool)
    if single:
        seq = [values]
    else:
        try:
            seq = list(values)
        except TypeError:
            raise ValueError(
                f"to_limbs: values must be an int or a sequence of ints, "
                f"got {type(values).__name__}") from None
    checked = []
    for i, v in enumerate(seq):
        where = "values" if single else f"values[{i}]"
        if isinstance(v, bool):
            raise ValueError(f"to_limbs: {where} must be an int, got a bool")
        try:
            v = operator.index(v)
        except TypeError:
            raise ValueError(
                f"to_limbs: {where} must be an int, got "
                f"{type(v).__name__}") from None
        if v < 0:
            raise ValueError(f"to_limbs: {where} must be >= 0, got {v}")
        if v.bit_length() > nbits:
            raise ValueError(
                f"to_limbs: {where} needs {v.bit_length()} bits but "
                f"nbits={nbits}")
        checked.append(v)
    if single:
        return _L.int_to_limbs(checked[0], m, 32)
    return _L.ints_to_batch(checked, m, 32)


def from_limbs(arr) -> "int | list[int]":
    """uint32 limb array -> python int ((m,)) or list of ints ((..., m),
    flattened over the leading axes in C order)."""
    a = np.asarray(arr, np.uint32)
    if a.ndim == 1:
        return _L.limbs_to_int(a, 32)
    return _L.batch_to_ints(a.reshape(-1, a.shape[-1]), 32)


def _digits_from_limbs(x, m_digits: int) -> jax.Array:
    """(..., ma) 32-bit limbs -> (..., m_digits) 16-bit digits (pad or
    truncate; truncated digits must be zero -- values < the modulus)."""
    d = _mul.split_digits(jnp.asarray(x, U32), DIGIT_BITS)
    n = d.shape[-1]
    if n < m_digits:
        pad = [(0, 0)] * (d.ndim - 1) + [(0, m_digits - n)]
        return jnp.pad(d, pad)
    return d[..., :m_digits]


def _limbs_from_digits(d, ma: int) -> jax.Array:
    return _mul.join_digits(d, DIGIT_BITS, ma)


def _limb_width(ctx) -> int:
    return -(-(ctx.m * DIGIT_BITS) // 32)


# ---------------------------------------------------------------------------
# arithmetic front doors
# ---------------------------------------------------------------------------

def mul(a, b, *, method: str = "auto") -> jax.Array:
    """Full product: (..., m) x (..., m) uint32 limbs -> (..., 2m).

    ``method``: "auto" (size/batch dispatch) or one of
    core/mul.MUL_METHODS.  Under ``configure(selfcheck=...)`` the result
    is verified against the mod-p residue product identity (one fold per
    operand, see repro/resilience/selfcheck.py)."""
    out = _mul.mul_limbs32(a, b, method=method)
    from repro.resilience import selfcheck as _sc
    _sc.check_mul(a, b, out)
    return out


def divmod(a, b, *, method: str = "auto",
           b_const: int | None = None):  # noqa: A001 - facade name
    """Exact floor (quotient, remainder): (..., ma) // (..., mb) uint32
    limbs -> ((..., ma), (..., mb)).  ``method``: "auto" or one of
    core/div.DIV_METHODS.  ``b_const`` declares the divisor a host-known
    constant (b must hold that value in every lane): the reciprocal
    path's fixed-operand multiplies then reuse cached forward NTTs
    (see cache_stats()["operand"]).  Under ``configure(selfcheck=...)``
    the result is verified against the residue identity
    res(q)*res(b) + res(r) == res(a)."""
    q, r = _div.divmod_limbs32(a, b, method=method, b_const=b_const)
    from repro.resilience import selfcheck as _sc
    _sc.check_divmod(a, b, q, r)
    return q, r


def to_decimal(x, n_dec: int) -> jax.Array:
    """(..., m) uint32 limbs -> (..., n_dec) base-10 digits, most
    significant first (on-device divide-and-conquer base conversion)."""
    return _div.to_decimal_limbs32(x, n_dec)


def mod_exp(base, exponent, modulus, *, backend: str | None = None,
            window: int | None = None, nbits: int | None = None
            ) -> jax.Array:
    """base ** exponent mod modulus on (..., m) uint32 limb arrays.

    ``modulus``: python int, or a prebuilt context from ``mod_setup``
    (build once per modulus when serving -- setup is host-side work).
    ``exponent``: python int (converted host-side), or a (..., nbits)
    MSB-first bit array for per-lane exponents.  ``base`` lanes must be
    < modulus.  ``backend=None`` auto-dispatches (fused Pallas ladder
    for kernel-sized batches); ``nbits`` pads the modulus width (shape
    bucketing -- requests of different widths share one trace)."""
    ctx = _M.mod_setup(modulus, nbits) if isinstance(modulus, int) \
        else modulus
    eb = _M.exp_bits_msb(exponent) if isinstance(exponent, int) \
        else exponent
    d = _digits_from_limbs(base, ctx.m)
    out = _M.mod_exp(d, jnp.asarray(eb), ctx, backend=backend,
                     window=window)
    out = _limbs_from_digits(out, _limb_width(ctx))
    from repro.resilience import selfcheck as _sc
    if _sc.enabled() and isinstance(exponent, int) \
            and not _sc._any_tracer(base, out):
        # modexp has no residue identity (see selfcheck.py): the check
        # is an exact host pow() witness per lane -- the documented cost
        # of verifying an op with no cheap public inverse
        mw = np.shape(base)[-1]
        b_np = np.asarray(base, np.uint32).reshape(-1, mw)
        o_np = np.asarray(out, np.uint32)
        o2 = o_np.reshape(-1, o_np.shape[-1])
        bad = sum(
            1 for i in range(o2.shape[0])
            if _L.limbs_to_int(o2[i], 32) != pow(
                _L.limbs_to_int(b_np[i % b_np.shape[0]], 32),
                exponent, ctx.n))
        if bad:
            _sc.report("mod_exp", bad, "host pow witness")
    return out


# ---------------------------------------------------------------------------
# RSA front doors
# ---------------------------------------------------------------------------

def rsa_sign(msg, key: "_rsa.RSAKey | _rsa.KeyLanes", *,
             backend: str | None = None) -> jax.Array:
    """s = m ** d mod n on (..., ma) uint32 limbs (ma = ceil(bits/32)).

    ``key``: one ``RSAKey`` for every lane, or a ``KeyLanes``
    (``key_lanes(keys)``) of keys of one width, one a lane of an (N, ma)
    batch: lane i is then signed under key i, in the same batch and the
    same compiled program."""
    ctx = key.ctx
    d = _digits_from_limbs(msg, ctx.m)
    return _limbs_from_digits(_rsa.sign(d, key, backend=backend),
                              _limb_width(ctx))


def rsa_verify(sig, key: "_rsa.RSAKey | _rsa.KeyLanes", *,
               backend: str | None = None) -> jax.Array:
    """m = s ** e mod n on (..., ma) uint32 limbs; ``key`` as for
    ``rsa_sign``."""
    ctx = key.ctx
    d = _digits_from_limbs(sig, ctx.m)
    return _limbs_from_digits(_rsa.verify(d, key, backend=backend),
                              _limb_width(ctx))


def rsa_decrypt(cipher, key: "_rsa.RSAKey", *, backend: str | None = None,
                crt: bool = True) -> jax.Array:
    """m = c ** d mod n on (..., ma) uint32 limbs.  ``crt=True`` (needs
    a key with known p, q) runs the two half-size CRT modexps; False
    falls back to the full-width ladder (== rsa_sign).  One key for the
    whole batch."""
    if not isinstance(key, _rsa.RSAKey):
        raise TypeError("rsa_decrypt takes one RSAKey for the whole batch")
    ctx = key.ctx
    d = _digits_from_limbs(cipher, ctx.m)
    if crt:
        out = _rsa.decrypt_crt(d, key, backend=backend)[..., :ctx.m]
    else:
        out = _rsa.sign(d, key, backend=backend)
    return _limbs_from_digits(out, _limb_width(ctx))


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

_UNSET = object()


class _ConfigureContext:
    """Returned by configure(): a no-op unless used as a context
    manager, in which case __exit__ restores the previous overrides."""

    def __init__(self, prev: dict):
        self._prev = prev

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        _config.set_overrides(self._prev)
        return False


def configure(*, mul_method=_UNSET, div_method=_UNSET,
              modexp_backend=_UNSET, autotune=_UNSET,
              ntt_cache_entries=_UNSET, observability=_UNSET,
              on_retrace=_UNSET, selfcheck=_UNSET,
              kernel_fallback=_UNSET) -> _ConfigureContext:
    """Override dispatch decisions, process-wide or scoped.

    Keyword-only; omitted knobs are left untouched, ``None`` clears an
    override (back to env alias, then heuristics):

      * ``mul_method``      one of core/mul.MUL_METHODS,
      * ``div_method``      one of core/div.DIV_METHODS,
      * ``modexp_backend``  one of core/modular.BACKENDS,
      * ``autotune``        bool -- enable the kernel tile sweep,
      * ``ntt_cache_entries``  int >= 0 -- LRU capacity of the
        prepared-operand NTT cache (kernels/ntt_mul); 0 disables the
        prepared path entirely (the A/B switch benchmarks use), None
        restores the default (see kernels/ntt_mul/ops.
        DEFAULT_CACHE_ENTRIES),
      * ``observability``   bool -- master switch for repro.obs
        (dispatch-trace events, spans, engine metric ticking); off by
        default so instrumentation costs nothing on hot paths,
      * ``on_retrace``      "ignore" / "warn" / "raise" -- the
        retrace-alarm policy when an armed zero-retrace contract sees
        a fresh jit trace (default "warn"; the ``retraces_total``
        counter ticks under every policy, see repro/obs/retrace.py),
      * ``selfcheck``       None/False (off, the default) or "warn" /
        "raise" -- verify mul/divmod results against mod-p residue
        identities and mod_exp / engine crypto results against host
        witnesses; failures tick ``selfcheck_failures_total`` under
        every policy (see repro/resilience/selfcheck.py),
      * ``kernel_fallback`` bool -- False/None (the default) is strict
        mode: the first kernel failure propagates, so a kernel that
        does not lower is an error, never a silent jnp run; True
        degrades a failing Pallas tier through jnp to the host
        reference (and lets the serving engine demote a failing
        bucket) so every request still answers, see
        repro/resilience/guard.py.

    Returns a context manager: ``with configure(...):`` restores the
    previous values on exit; a bare call applies them permanently.
    Replaces the deprecated REPRO_* env vars (still honored, one
    DeprecationWarning each, at lower precedence)."""
    updates: dict = {}
    if mul_method is not _UNSET:
        if mul_method is not None and mul_method not in _mul.MUL_METHODS:
            raise ValueError(
                f"unknown multiply method {mul_method!r}; choose from "
                f"{_mul.MUL_METHODS}")
        updates["mul_method"] = mul_method
    if div_method is not _UNSET:
        if div_method is not None and div_method not in _div.DIV_METHODS:
            raise ValueError(
                f"unknown division method {div_method!r}; choose from "
                f"{_div.DIV_METHODS}")
        updates["div_method"] = div_method
    if modexp_backend is not _UNSET:
        if modexp_backend is not None \
                and modexp_backend not in _M.BACKENDS:
            raise ValueError(
                f"unknown backend {modexp_backend!r}; choose from "
                f"{_M.BACKENDS}")
        updates["modexp_backend"] = modexp_backend
    if autotune is not _UNSET:
        if autotune is not None and not isinstance(autotune, bool):
            raise ValueError(
                f"autotune must be a bool or None, got {autotune!r}")
        updates["autotune"] = autotune
    if ntt_cache_entries is not _UNSET:
        if ntt_cache_entries is not None and (
                not isinstance(ntt_cache_entries, int)
                or isinstance(ntt_cache_entries, bool)
                or ntt_cache_entries < 0):
            raise ValueError(
                f"ntt_cache_entries must be an int >= 0 or None, got "
                f"{ntt_cache_entries!r}")
        updates["ntt_cache_entries"] = ntt_cache_entries
    if observability is not _UNSET:
        if observability is not None and not isinstance(observability, bool):
            raise ValueError(
                f"observability must be a bool or None, got "
                f"{observability!r}")
        updates["observability"] = observability
    if on_retrace is not _UNSET:
        from repro.obs import retrace as _rt
        if on_retrace is not None and on_retrace not in _rt.POLICIES:
            raise ValueError(
                f"unknown on_retrace policy {on_retrace!r}; choose from "
                f"{_rt.POLICIES}")
        updates["on_retrace"] = on_retrace
    if selfcheck is not _UNSET:
        from repro.resilience import selfcheck as _sc
        if selfcheck not in (None, False) and selfcheck not in _sc.POLICIES:
            raise ValueError(
                f"unknown selfcheck policy {selfcheck!r}; choose from "
                f"{_sc.POLICIES} (or None/False to disable)")
        updates["selfcheck"] = selfcheck
    if kernel_fallback is not _UNSET:
        if kernel_fallback is not None \
                and not isinstance(kernel_fallback, bool):
            raise ValueError(
                f"kernel_fallback must be a bool or None, got "
                f"{kernel_fallback!r}")
        updates["kernel_fallback"] = kernel_fallback
    return _ConfigureContext(_config.set_overrides(updates))


def cache_stats() -> dict:
    """Hit/miss/size counters for every process-level arithmetic cache:

      * ``twiddle``  -- the lru_cache of per-(prime, N) NTT twiddle
        tables (kernels/ntt_mul.lane_twiddles),
      * ``operand``  -- the prepared-operand NTT cache (forward
        transforms of host-known constants, LRU-bounded by
        ``configure(ntt_cache_entries=...)``),
      * ``autotune`` -- the kernel tile-sweep cache (hits/misses only
        tick while ``configure(autotune=True)``),
      * ``ctx``      -- the memoized host-side modulus contexts
        (core/modular.mont_setup / barrett_setup lru_caches; the
        ``_as_barrett`` promotion path answers from the barrett_setup
        cache, so its reuse shows up there).

    Returns plain dicts of ints -- cheap to call, safe to log from
    serving loops; the ops knob for verifying that repeat-operand work
    is actually being reused (a cold ``operand`` cache under a
    repeat-multiply-by-constant workload means b_const isn't being
    threaded; churning ``ctx`` misses under a finite key set means
    contexts are being rebuilt per call)."""
    from repro.kernels.common import autotune as _at
    from repro.kernels.ntt_mul import ops as _nops

    def _lru(info):
        return {"hits": info.hits, "misses": info.misses,
                "entries": info.currsize, "capacity": info.maxsize}

    return {
        "twiddle": _lru(_nops.lane_twiddles.cache_info()),
        "operand": _nops.operand_cache_stats(),
        "autotune": _at.cache_stats(),
        "ctx": {
            "mont_setup": _lru(_M.mont_setup.cache_info()),
            "barrett_setup": _lru(_M.barrett_setup.cache_info()),
        },
    }


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def metrics() -> dict:
    """Snapshot of the process metrics registry (repro/obs/metrics.py)
    plus the arithmetic cache counters.

    ``{"counters": {name: {labels: value}}, "gauges": ...,
    "histograms": {name: {labels: {count/sum/min/max/p50/p95/p99}}},
    "caches": cache_stats(), "breaker": ...}`` -- JSON-serializable, so
    serving loops and CI can dump it as an artifact.  Dispatch/span/
    latency series only populate while ``configure(observability=True)``;
    the ``retraces_total`` counter and the resilience series
    (``fallback_total`` / ``shed_total`` / ``deadline_miss_total`` /
    ``breaker_state`` / ``selfcheck_failures_total``) tick regardless
    (runtime contracts, not debug detail -- see repro/obs/retrace.py and
    repro/resilience/).  ``breaker`` is the circuit-breaker snapshot:
    every quarantined (op, shape-bucket, backend) key with its state and
    time-to-retry, plus any forced-open patterns."""
    from repro.obs import metrics as _om
    from repro.resilience.breaker import BREAKER as _breaker

    snap = _om.REGISTRY.snapshot()
    snap["caches"] = cache_stats()
    snap["breaker"] = _breaker.snapshot()
    return snap


def dispatch_report() -> list:
    """Aggregated dispatch-trace rows ({dispatcher, nbits, batch,
    choice, rule, detail, count}) from the bounded event buffer --
    which backend each tier chooser picked and WHICH threshold fired.
    Empty unless ``configure(observability=True)`` was on while the
    workload dispatched (decisions are recorded at trace time, so a
    jit-cached replay emits nothing new).  Render with
    ``repro.obs.format_report()``."""
    from repro.obs import trace as _ot

    return _ot.report()
