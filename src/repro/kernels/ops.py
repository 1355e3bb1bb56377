"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU (the kernel body executes in Python
for validation); on TPU backends it defaults to False (compiled Mosaic).

Dispatch instrumentation (``docs/observability.md``): after
:func:`instrument`, every public wrapper records per-op call counts and
cumulative host-side dispatch time into a ``repro.obs.MetricsRegistry``
(``kernel_dispatch_calls_total`` / ``kernel_dispatch_seconds_total``,
labeled by op), and the fused-vs-ref dispatch decisions made one level
up in ``core.quantize`` land in ``quant_dispatch_total{op,path}``. Calls
made *inside* an enclosing ``jax.jit`` trace execute once per compile,
not once per step — they are labeled ``traced="true"`` so compile-time
inlines and real dispatches never sum into each other. Uninstrumented
(the default), the wrappers add a single ``is None`` check per call.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from . import hadamard_quant as _hq
from . import mx_attention as _ma
from . import mx_matmul as _mm
from . import mx_quant as _mq
from . import packing as _pk
from . import ref


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------------------
# Dispatch instrumentation
# ----------------------------------------------------------------------

_instr = None       # (registry, tracer) when instrumented


def instrument(registry, tracer=None) -> None:
    """Start recording kernel-dispatch metrics into ``registry`` (a
    ``repro.obs.MetricsRegistry``); optionally also emit a
    ``dispatch:<op>`` span per python-level call when a
    ``repro.obs.Tracer`` is given. Global (module-level) — one
    instrumentation target at a time; :func:`uninstrument` stops."""
    global _instr
    _instr = (registry, tracer)


def uninstrument() -> None:
    global _instr
    _instr = None


def _is_traced(*xs) -> bool:
    return any(isinstance(x, jax.core.Tracer) for x in xs)


def _record(op: str, dt: float, traced: bool) -> None:
    registry, _ = _instr
    labels = {"op": op, "traced": "true" if traced else "false"}
    registry.counter(
        "kernel_dispatch_calls_total", labels,
        help="public kernel-wrapper invocations (traced=true rows ran "
             "inside an enclosing jit trace: once per compile, not per "
             "step)").inc()
    registry.counter(
        "kernel_dispatch_seconds_total", labels, unit="s",
        help="cumulative host-side dispatch wall time (async device "
             "work excluded; under interpret mode this is ~the actual "
             "kernel time)").inc(dt)


def _dispatch(op: str, fn, *args, **kwargs):
    """Call ``fn`` (the jitted implementation), timing the host-side
    dispatch when instrumented. The timer spans trace+dispatch only —
    device execution is asynchronous and deliberately NOT waited on (no
    host sync is ever added to a serving hot loop by instrumentation)."""
    ins = _instr
    if ins is None:
        return fn(*args, **kwargs)
    traced = _is_traced(*args)
    _, tracer = ins
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    dt = time.perf_counter() - t0
    _record(op, dt, traced)
    if tracer is not None and not traced:
        tracer.complete(f"dispatch:{op}", t0, t0 + dt, cat="kernel")
    return out


def record_quant_path(op: str, path: str, role: str = "") -> None:
    """Hook for ``core.quantize``: count a fused-vs-ref dispatch
    decision (``quant_dispatch_total{op, path, role}``). No-op unless
    :func:`instrument` is active. Runs at trace time for calls inside a
    jit — counts are per *compiled call site*, not per step."""
    ins = _instr
    if ins is None:
        return
    ins[0].counter(
        "quant_dispatch_total", {"op": op, "path": path, "role": role},
        help="qlinear/qeinsum execution-path decisions (per traced "
             "call site)").inc()


@functools.partial(jax.jit, static_argnames=("fmt", "interpret"))
def _mx_quantize_jit(x, fmt: str = "mxfp4",
                     interpret: bool | None = None):
    it = _default_interpret() if interpret is None else interpret
    return _mq.mx_quant(x, fmt, interpret=it)


def mx_quantize(x, fmt: str = "mxfp4", interpret: bool | None = None):
    return _dispatch("mx_quantize", _mx_quantize_jit, x, fmt=fmt,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("fmt", "interpret"))
def _mx_gemm_jit(x, w_codes, w_scales, fmt: str = "mxfp4",
                 interpret: bool | None = None):
    it = _default_interpret() if interpret is None else interpret
    return _mm.mx_matmul(x, w_codes, w_scales, fmt, interpret=it)


def mx_gemm(x, w_codes, w_scales, fmt: str = "mxfp4",
            interpret: bool | None = None):
    return _dispatch("mx_gemm", _mx_gemm_jit, x, w_codes, w_scales,
                     fmt=fmt, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("fmt", "interpret"))
def _t3_quantize_jit(x, fmt: str = "mxfp4",
                     interpret: bool | None = None):
    it = _default_interpret() if interpret is None else interpret
    return _hq.hadamard_quant(x, fmt, interpret=it)


def t3_quantize(x, fmt: str = "mxfp4", interpret: bool | None = None):
    return _dispatch("t3_quantize", _t3_quantize_jit, x, fmt=fmt,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("fmt", "t3", "interpret"))
def _mx_gemm_packed_jit(x, w_packed, w_scales_e8m0, fmt: str = "mxfp4",
                        t3: bool = False,
                        interpret: bool | None = None):
    """Packed-native fused MX GEMM over the HBM layout (PackedWeight
    arrays): nibble-packed codes + E8M0 scale bytes in, fp32 out.

    Shapes/dtypes (2-D): x (M, K) float (f32/bf16 — quantized to ``fmt``
    on the fly in the kernel prologue); w_packed (K//2, N) uint8 (two
    4-bit codes per byte along the contraction axis); w_scales_e8m0
    (K//32, N) uint8 (one pow2 scale byte per 32-block). Returns (M, N)
    float32 — no dense fp weight is ever materialized. K must be a
    multiple of 32. Stacked (layer- or expert-batched) weights carry
    leading batch dims on all three operands and are mapped with
    ``jax.vmap`` (a leading grid axis on TPU); x must then be
    (*lead, M, K) — rank mismatches raise ValueError.

    t3=True folds the online 32-wide T3 block-Hadamard into the
    activation-quantize prologue (the ``ffn_down`` call-site). fmt must
    be a packable format ('mxfp4' | 'mxint4').

    This is the raw kernel wrapper: eligibility checks and the
    bit-identical fallback to the reference path live one level up in
    ``core.quantize.qlinear`` / ``qeinsum`` — callers that cannot meet
    the contract should go through those. Off-TPU the kernel executes in
    interpret mode (correct, slow) unless ``interpret`` is forced.
    """
    it = _default_interpret() if interpret is None else interpret
    fn = functools.partial(_mm.mx_matmul_packed, fmt=fmt, t3=t3,
                           interpret=it)
    lead = w_packed.ndim - 2
    if x.ndim != lead + 2:
        raise ValueError(f"x rank {x.ndim} does not match weight batch "
                         f"rank {w_packed.ndim}")
    for _ in range(lead):
        fn = jax.vmap(fn)
    return fn(x, w_packed, w_scales_e8m0)


def mx_gemm_packed(x, w_packed, w_scales_e8m0, fmt: str = "mxfp4",
                   t3: bool = False, interpret: bool | None = None):
    return _dispatch("mx_gemm_packed", _mx_gemm_packed_jit, x, w_packed,
                     w_scales_e8m0, fmt=fmt, t3=t3, interpret=interpret)


mx_gemm_packed.__doc__ = _mx_gemm_packed_jit.__doc__


def _flash_decode_contract(q, k_codes, k_scales, v_codes,
                           v_scales, fmt: str) -> bool:
    """Does the packed KV meet the Pallas flash-decode kernel contract?"""
    if fmt not in _pk.KV_FMTS:
        return False
    if q.ndim != 3 or k_codes.ndim != 3 or k_scales.ndim != 3:
        return False
    B, H, Dh = q.shape
    bits = _pk.kv_fmt_bits(fmt)
    D = k_codes.shape[2] * 8 // bits
    if D % 32 != 0 or Dh == 0 or D % Dh != 0 or H % (D // Dh) != 0:
        return False
    return (k_codes.shape[0] == B
            and k_scales.shape == (B, k_codes.shape[1], D // 32)
            and v_codes.shape == k_codes.shape
            and v_scales.shape == k_scales.shape)


@functools.partial(jax.jit,
                   static_argnames=("fmt", "window", "bs", "interpret"))
def _mx_flash_decode_jit(q, k_codes, k_scales, v_codes, v_scales, q_pos,
                         kv_len, fmt: str = "mxfp8", window: int = 0,
                         bs: int | None = None,
                         interpret: bool | None = None):
    """Flash-decode attention over a packed MX KV cache.

    Shapes/dtypes: q (B, H, Dh) float — one decode token per lane;
    k/v_codes (B, S, D*bits/8) uint8 and k/v_scales (B, S, D//32) uint8
    E8M0 bytes in the ``packing.PackedKV`` layout (D = n_kv_heads * Dh,
    nibble-packed along the feature axis for 4-bit fmts); q_pos / kv_len
    (B,) int32 (scalars broadcast). Keys are contiguous from position 0.
    Returns (B, H, Dh) float32. ``window`` > 0 masks keys at
    ``pos <= q_pos - window`` (sliding-window attention).

    Dispatch: the Pallas kernel consumes the packed bytes directly
    (decoded per KV chunk in VMEM, online softmax with GQA and per-lane
    masking). Anything off-contract — a non-KV format, a mismatched
    scale layout, a head count the GQA view cannot tile — is rejected
    with a ValueError: every such input is equally ill-formed for the
    jnp oracle, so there is no graceful fallback to route to. The
    *model-level* fallback lives in ``models.layers.attention``: caches
    the kernel cannot serve (ring buffers, chunked prefill, the 'ref'
    backend) are decoded in place and run the dense jnp path. Off-TPU
    the kernel executes in interpret mode (correct, slow) unless
    ``interpret`` is forced.

    ``bs`` (KV chunk width) defaults to the whole cache under interpret
    mode — the chunk grid exists for the TPU memory hierarchy, and an
    interpreted grid step is pure overhead — and to a VMEM-sized tile
    when compiled. An *explicit* ``bs`` is honored exactly (it must
    divide S, else ValueError) on every backend, so the multi-chunk grid
    is exercisable in CPU interpret mode too.
    """
    if not _flash_decode_contract(q, k_codes, k_scales, v_codes,
                                  v_scales, fmt):
        raise ValueError(
            f"mx_flash_decode contract violation: q {q.shape}, k_codes "
            f"{k_codes.shape}, k_scales {k_scales.shape}, v_codes "
            f"{v_codes.shape}, v_scales {v_scales.shape}, fmt={fmt!r}. "
            f"Expected q (B, H, Dh); codes (B, S, D*bits/8) with "
            f"D % 32 == 0, D % Dh == 0 and H divisible by the kv-head "
            f"count D/Dh; scales (B, S, D//32); V shapes matching K; "
            f"fmt one of {_pk.KV_FMTS}.")
    it = _default_interpret() if interpret is None else interpret
    explicit = bs is not None
    if bs is None:
        bs = k_codes.shape[1] if it else 512
    return _ma.mx_flash_decode(q, k_codes, k_scales, v_codes, v_scales,
                               q_pos, kv_len, fmt, window=window, bs=bs,
                               explicit_bs=explicit, interpret=it)


def mx_flash_decode(q, k_codes, k_scales, v_codes, v_scales, q_pos,
                    kv_len, fmt: str = "mxfp8", window: int = 0,
                    bs: int | None = None,
                    interpret: bool | None = None):
    return _dispatch("mx_flash_decode", _mx_flash_decode_jit, q, k_codes,
                     k_scales, v_codes, v_scales, q_pos, kv_len, fmt=fmt,
                     window=window, bs=bs, interpret=interpret)


mx_flash_decode.__doc__ = _mx_flash_decode_jit.__doc__


def _flash_decode_paged_contract(q, k_codes, k_scales, v_codes, v_scales,
                                 block_tables, fmt: str) -> bool:
    """Does the page pool meet the paged flash-decode kernel contract?"""
    if fmt not in _pk.KV_FMTS:
        return False
    if (q.ndim != 3 or k_codes.ndim != 3 or k_scales.ndim != 3
            or block_tables.ndim != 2):
        return False
    B, H, Dh = q.shape
    bits = _pk.kv_fmt_bits(fmt)
    N, P = k_codes.shape[0], k_codes.shape[1]
    D = k_codes.shape[2] * 8 // bits
    if D % 32 != 0 or Dh == 0 or D % Dh != 0 or H % (D // Dh) != 0:
        return False
    return (block_tables.shape[0] == B
            and k_scales.shape == (N, P, D // 32)
            and v_codes.shape == k_codes.shape
            and v_scales.shape == k_scales.shape)


@functools.partial(jax.jit, static_argnames=("fmt", "window", "interpret"))
def _mx_flash_decode_paged_jit(q, k_codes, k_scales, v_codes, v_scales,
                               block_tables, q_pos, kv_len,
                               fmt: str = "mxfp8", window: int = 0,
                               interpret: bool | None = None):
    """Flash-decode attention over a *paged* packed MX KV pool.

    Shapes/dtypes: q (B, H, Dh) float; k/v_codes (N, P, D*bits/8) uint8
    and k/v_scales (N, P, D//32) uint8 E8M0 bytes — the shared page pool
    in the ``packing.PagedKV`` layout (N pages of P tokens each);
    block_tables (B, maxp) int32 — lane b's chunk c reads pool page
    ``block_tables[b, c]``, which holds logical positions
    [c*P, (c+1)*P); q_pos / kv_len (B,) int32 (scalars broadcast).
    Returns (B, H, Dh) float32. ``window`` as in :func:`mx_flash_decode`.

    The block table is a scalar-prefetch operand: BlockSpec index maps
    resolve the page id before each grid step, so the kernel DMA-gathers
    pages straight from the pool — no contiguous copy of a lane's cache
    is ever materialized. Table slots past a lane's fill must still hold
    *valid* page ids (the serving engine parks them on its scrap page);
    those rows are masked by ``kv_len``. Off-contract inputs raise — the
    model-level fallback (gather + dense jnp attention) lives in
    ``models.layers.attention_paged``."""
    if not _flash_decode_paged_contract(q, k_codes, k_scales, v_codes,
                                        v_scales, block_tables, fmt):
        raise ValueError(
            f"mx_flash_decode_paged contract violation: q {q.shape}, "
            f"k_codes {k_codes.shape}, k_scales {k_scales.shape}, "
            f"v_codes {v_codes.shape}, v_scales {v_scales.shape}, "
            f"block_tables {block_tables.shape}, fmt={fmt!r}. Expected "
            f"q (B, H, Dh); a (N, P, D*bits/8) page pool with "
            f"D % 32 == 0, D % Dh == 0 and H divisible by the kv-head "
            f"count D/Dh; scales (N, P, D//32); V shapes matching K; "
            f"block_tables (B, maxp) int32; fmt one of {_pk.KV_FMTS}.")
    it = _default_interpret() if interpret is None else interpret
    return _ma.mx_flash_decode_paged(q, k_codes, k_scales, v_codes,
                                     v_scales, block_tables, q_pos,
                                     kv_len, fmt, window=window,
                                     interpret=it)


def mx_flash_decode_paged(q, k_codes, k_scales, v_codes, v_scales,
                          block_tables, q_pos, kv_len,
                          fmt: str = "mxfp8", window: int = 0,
                          interpret: bool | None = None):
    return _dispatch("mx_flash_decode_paged", _mx_flash_decode_paged_jit,
                     q, k_codes, k_scales, v_codes, v_scales,
                     block_tables, q_pos, kv_len, fmt=fmt, window=window,
                     interpret=interpret)


mx_flash_decode_paged.__doc__ = _mx_flash_decode_paged_jit.__doc__


def _flash_prefill_contract(q, k_chunk, v_chunk, k_codes, k_scales,
                            v_codes, v_scales, block_tables,
                            fmt: str) -> bool:
    """Does the input meet the paged flash-prefill kernel contract?"""
    if fmt not in _pk.KV_FMTS:
        return False
    if (q.ndim != 4 or k_chunk.ndim != 3 or k_codes.ndim != 3
            or k_scales.ndim != 3 or block_tables.ndim != 2):
        return False
    B, C, H, Dh = q.shape
    bits = _pk.kv_fmt_bits(fmt)
    N, P = k_codes.shape[0], k_codes.shape[1]
    D = k_codes.shape[2] * 8 // bits
    if D % 32 != 0 or Dh == 0 or D % Dh != 0 or H % (D // Dh) != 0:
        return False
    return (block_tables.shape[0] == B and block_tables.shape[1] >= 1
            and k_chunk.shape == (B, C, D)
            and v_chunk.shape == k_chunk.shape
            and k_scales.shape == (N, P, D // 32)
            and v_codes.shape == k_codes.shape
            and v_scales.shape == k_scales.shape)


@functools.partial(jax.jit, static_argnames=("fmt", "window", "qb", "kvb",
                                             "interpret"))
def _mx_flash_prefill_jit(q, k_chunk, v_chunk, k_codes, k_scales, v_codes,
                          v_scales, block_tables, q_start, kv_len,
                          fmt: str = "mxfp8", window: int = 0,
                          qb: int | None = None, kvb: int | None = None,
                          interpret: bool | None = None):
    """Flash-prefill attention over a *paged* packed MX KV pool, fused
    with the quantize-on-append of the current chunk.

    Shapes/dtypes: q (B, C, H, Dh) float — a C-token prefill chunk per
    lane; k/v_chunk (B, C, D) float — the chunk's dense K/V (D =
    n_kv_heads * Dh); k/v_codes (N, P, D*bits/8) uint8 and k/v_scales
    (N, P, D//32) uint8 E8M0 bytes — the shared page pool in the
    ``packing.PagedKV`` layout; block_tables (B, maxp) int32 (same
    scalar-prefetch ABI as :func:`mx_flash_decode_paged`); q_start /
    kv_len (B,) int32 (scalars broadcast) — chunk start offset and
    valid-key bound per lane.

    Returns ``(out (B, C, H, Dh) f32, k_code_bytes (B, C, D*bits/8) u8,
    k_scale_bytes (B, C, D//32) u8, v_code_bytes, v_scale_bytes)``. The
    byte outputs are bit-identical to ``packing.kv_encode`` of the chunk
    — the caller scatters them into the pool
    (``models.layers.kv_scatter_chunk_paged``) so dense chunk K/V never
    round-trips HBM; the kernel attends the decoded roundtrip of those
    same bytes, keeping it bit-identical to write-then-read. Pool rows
    ``kp < q_start`` are the committed prefix; causal / fill / window
    masks are per query row, as in ``models.layers.attention``.

    Off-contract inputs raise ValueError — every such input is equally
    ill-formed for the jnp oracle (``mx_prefill_ref``); the model-level
    fallback (quantize + scatter + gather + dense jnp attention) lives in
    ``models.transformer.attn_sublayer_chunk_paged``. ``qb``/``kvb``
    (query/self-KV tile widths over the chunk) default to the whole chunk
    under interpret mode and to VMEM-sized tiles when compiled; explicit
    values are honored exactly (must divide C, else ValueError) on every
    backend, so the multi-block grid is exercisable in CPU interpret
    mode."""
    if not _flash_prefill_contract(q, k_chunk, v_chunk, k_codes, k_scales,
                                   v_codes, v_scales, block_tables, fmt):
        raise ValueError(
            f"mx_flash_prefill contract violation: q {q.shape}, k_chunk "
            f"{k_chunk.shape}, v_chunk {v_chunk.shape}, k_codes "
            f"{k_codes.shape}, k_scales {k_scales.shape}, v_codes "
            f"{v_codes.shape}, v_scales {v_scales.shape}, block_tables "
            f"{block_tables.shape}, fmt={fmt!r}. Expected q (B, C, H, "
            f"Dh); dense chunk K/V (B, C, D) with D % 32 == 0, "
            f"D % Dh == 0 and H divisible by the kv-head count D/Dh; a "
            f"(N, P, D*bits/8) page pool with scales (N, P, D//32); V "
            f"shapes matching K; block_tables (B, maxp) int32 with "
            f"maxp >= 1; fmt one of {_pk.KV_FMTS}.")
    it = _default_interpret() if interpret is None else interpret
    C, H, Dh = q.shape[1], q.shape[2], q.shape[3]
    G = H * Dh // k_chunk.shape[2]
    explicit_qb = qb is not None
    explicit_kvb = kvb is not None
    if qb is None:      # compiled: ~128 (query, group-head) rows per block
        qb = C if it else max(8, 128 // G)
    if kvb is None:
        kvb = C if it else 128
    return _ma.mx_flash_prefill(q, k_chunk, v_chunk, k_codes, k_scales,
                                v_codes, v_scales, block_tables, q_start,
                                kv_len, fmt, window=window, qb=qb,
                                kvb=kvb, explicit_qb=explicit_qb,
                                explicit_kvb=explicit_kvb, interpret=it)


def mx_flash_prefill(q, k_chunk, v_chunk, k_codes, k_scales, v_codes,
                     v_scales, block_tables, q_start, kv_len,
                     fmt: str = "mxfp8", window: int = 0,
                     qb: int | None = None, kvb: int | None = None,
                     interpret: bool | None = None):
    return _dispatch("mx_flash_prefill", _mx_flash_prefill_jit, q,
                     k_chunk, v_chunk, k_codes, k_scales, v_codes,
                     v_scales, block_tables, q_start, kv_len, fmt=fmt,
                     window=window, qb=qb, kvb=kvb, interpret=interpret)


mx_flash_prefill.__doc__ = _mx_flash_prefill_jit.__doc__


# re-exported oracles
mx_quant_ref = ref.mx_quant_ref
mx_matmul_ref = ref.mx_matmul_ref
mx_matmul_packed_ref = ref.mx_matmul_packed_ref
mx_attention_ref = ref.mx_attention_ref
mx_attention_paged_ref = ref.mx_attention_paged_ref
mx_prefill_ref = ref.mx_prefill_ref
hadamard_quant_ref = ref.hadamard_quant_ref
quantize_weight_for_kernel = ref.quantize_weight_for_kernel
