"""Multi-chip dispatch for the Pallas kernel set.

The reference runs its fused CUDA kernels under the multi-device executor
(``paddle/fluid/operators/fused/multihead_matmul_op.cu`` launched per
device by ``framework/parallel_executor.cc:504``). The TPU-native
equivalent: each Pallas call-unit runs *per shard* inside a
``jax.shard_map`` over the ambient mesh, embedded in the automatically
partitioned jit around it, instead of falling back to the dense jnp
path. (``jax.experimental.custom_partitioning`` — the earlier mechanism
— does not compile on the TPU backend: libtpu answers "Custom emitter
for CustomSPMDPartitioning not found".)

Design per unit:

- a **plan** decides, from the mesh and the operand shapes alone, which
  dims shard over which mesh axes. It follows the framework-wide layout
  (``parallel/mesh.py``, the table in ``models/llama.py``): batch-like
  dims over the data axes (dp, fsdp), head / vocab / channel dims over
  tp, a RoPE sequence dim over sp; whatever the kernel reduces over or
  tiles on stays replicated. A dim shards only when the axes divide it
  (GQA: both head counts; batch: the batch). An operand that arrives
  laid out differently is resharded by the partitioner at the shard_map
  boundary, exactly as a ``with_sharding_constraint`` would — e.g. a
  ZeRO-3-sharded lm-head weight is all-gathered for the fused loss, as
  the dense matmul path would gather it;
- the **per-shard body** calls the raw kernel on local shapes, with a
  jnp fallback when a shard's row count breaks the kernel's block
  alignment, and emits the cross-shard collectives (psum of dw/db,
  log-sum-exp combine over a sharded vocab) itself.

Factories are keyed on the static config (lru_cache) so one callable is
reused per (causal, scale, blocks, ...) combination.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel.mesh import BATCH_AXES, get_mesh

LANES = 128

# Per-shard lowering decisions, keyed "<unit>:<kernel|fallback>", counted
# as each unit is traced: "the Pallas path executed under sharding" is a
# checkable claim (chip_smoke.py, the multichip dryrun), not an
# assumption.
stats: collections.Counter = collections.Counter()


def reset_stats() -> None:
    stats.clear()


def _mod(name: str):
    """Submodule import immune to the package __init__ re-exporting a
    function under the same name (``pallas.flash_attention`` is the
    function once the package is initialized)."""
    import importlib
    return importlib.import_module(f"paddle_tpu.ops.pallas.{name}")


# ---------------------------------------------------------------------------
# small spec helpers
# ---------------------------------------------------------------------------

def _axes(entry) -> tuple:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def _size(mesh, entry) -> int:
    s = 1
    for a in _axes(entry):
        s *= mesh.shape[a]
    return s


def _live(mesh, *names):
    """The named mesh axes that exist with degree > 1, as a spec entry
    (None when there are none)."""
    live = tuple(a for a in names if mesh.shape.get(a, 1) > 1)
    return live or None


def _data(mesh):
    """Spec entry for a batch-like dim: the data axes (dp, fsdp)."""
    return _live(mesh, *BATCH_AXES)


def _rows_aligned(n_local: int, block: int) -> bool:
    """Kernel row blocks are min(block, n) — a shard is runnable when its
    row count still tiles (and stays sublane-aligned)."""
    if n_local <= 0 or n_local % 8:
        return False
    return n_local <= block or n_local % block == 0


def _valid_dim(mesh, entry, dim_size: int, used: set,
               multiple: int = 1) -> object:
    """Keep a dim sharding only if it divides the dim into shards that
    are a multiple of ``multiple`` (a kernel tile) and does not reuse an
    axis already consumed by another dim of the same spec."""
    ax = _axes(entry)
    if not ax or set(ax) & used:
        return None
    s = _size(mesh, entry)
    if s <= 1 or dim_size % s or (dim_size // s) % multiple:
        return None
    used.update(ax)
    return entry


def _build(body, plan):
    """Wire a pallas call-unit into shard_map over the ambient mesh.

    ``plan(mesh, args) -> (arg_specs, out_specs, ctx)`` makes the
    sharding decision (``out_specs`` mirrors the unit's return
    structure); ``body(ctx, *local_args)`` is the per-shard lowering
    (ctx carries the axes it must psum over / whether to take the jnp
    fallback).
    """
    def call(*args):
        mesh = get_mesh()
        arg_specs, out_specs, ctx = plan(mesh, args)
        return jax.shard_map(
            functools.partial(body, ctx), mesh=mesh,
            in_specs=tuple(arg_specs), out_specs=out_specs,
            check_vma=False)(*args)

    return call


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _batch_head_plan(mesh, B, Hq, Hkv):
    """Shared batch/head sharding selection for the attention units:
    batch over the data axes, heads over tp, everything else replicated.
    The head sharding must divide BOTH head counts so each shard keeps
    whole GQA groups (contiguous blocks: q heads [i·Hq/s, …) ↔ kv heads
    [i·Hkv/s, …))."""
    used: set = set()
    b = _valid_dim(mesh, _data(mesh), B, used)
    h = _valid_dim(mesh, _live(mesh, "tp"), math.gcd(Hq, Hkv), used)
    return b, h


def _flash_plan(mesh, args):
    B, Hq = args[0].shape[0], args[0].shape[1]
    Hkv = args[1].shape[1]
    return _batch_head_plan(mesh, B, Hq, Hkv)


@functools.lru_cache(maxsize=None)
def flash_fwd(causal: bool, scale: float, block_q, block_k):
    FA = _mod("flash_attention")

    def body(ctx, qt, kt, vt):
        stats["flash_fwd:kernel"] += 1
        return tuple(FA._fwd(qt, kt, vt, causal, scale, block_q, block_k))

    def plan(mesh, args):
        b, h = _flash_plan(mesh, args)
        io = P(b, h, None, None)
        return (io, io, io), (io, io), None

    return _build(body, plan)


@functools.lru_cache(maxsize=None)
def flash_bwd(causal: bool, scale: float, block_q, block_k):
    FA = _mod("flash_attention")

    def body(ctx, qt, kt, vt, ot, lse, do_t):
        stats["flash_bwd:kernel"] += 1
        return FA._bwd_impl(qt, kt, vt, ot, lse, do_t, causal, scale,
                            block_q, block_k)

    def plan(mesh, args):
        b, h = _flash_plan(mesh, args)
        io = P(b, h, None, None)
        return (io,) * 6, (io, io, io), None

    return _build(body, plan)


# ---------------------------------------------------------------------------
# row norms (rms / layer norm) — 2D [n, h] units
# ---------------------------------------------------------------------------

def _rows_plan(mesh, x_arg, block_rows):
    """Rows over the data axes; feature dim replicated. Returns (row
    entry, row axes for psum, use_kernel)."""
    n = x_arg.shape[0]
    r = _valid_dim(mesh, _data(mesh), n, set())
    return r, _axes(r), _rows_aligned(n // _size(mesh, r), block_rows)


@functools.lru_cache(maxsize=None)
def rms_fwd(eps: float):
    N = _mod("norm")

    def body(use_kernel, x2d, w):
        if use_kernel:
            stats["rms_fwd:kernel"] += 1
            return tuple(N._rms_fwd(x2d, w, eps))
        stats["rms_fwd:fallback"] += 1
        xf = x2d.astype(jnp.float32)
        rstd = jax.lax.rsqrt(jnp.mean(xf * xf, axis=1, keepdims=True) + eps)
        y = (xf * rstd * w.astype(jnp.float32)).astype(x2d.dtype)
        return y, jnp.broadcast_to(rstd, (x2d.shape[0], LANES))

    def plan(mesh, args):
        r, _, ok = _rows_plan(mesh, args[0], N._BLOCK_ROWS)
        return (P(r, None), P(None)), (P(r, None), P(r, None)), ok

    return _build(body, plan)


@functools.lru_cache(maxsize=None)
def rms_bwd(eps: float):
    N = _mod("norm")

    def body(ctx, x2d, w, rstd, g):
        raxes, use_kernel = ctx
        if use_kernel:
            stats["rms_bwd:kernel"] += 1
            dx, dw = N._rms_bwd_call(x2d, w, rstd, g)
        else:
            stats["rms_bwd:fallback"] += 1
            xf = x2d.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            wf = w.astype(jnp.float32)
            rs = rstd[:, :1]
            xhat = xf * rs
            wg = gf * wf
            c = jnp.mean(wg * xhat, axis=1, keepdims=True)
            dx = (rs * (wg - xhat * c)).astype(x2d.dtype)
            dw = jnp.sum(gf * xhat, axis=0)
        if raxes:
            dw = jax.lax.psum(dw, raxes)
        return dx, dw

    def plan(mesh, args):
        r, raxes, ok = _rows_plan(mesh, args[0], N._BLOCK_ROWS)
        return ((P(r, None), P(None), P(r, None), P(r, None)),
                (P(r, None), P(None)),
                (raxes, ok))

    return _build(body, plan)


@functools.lru_cache(maxsize=None)
def ln_fwd(eps: float):
    N = _mod("norm")

    def body(use_kernel, x2d, w, b):
        if use_kernel:
            stats["ln_fwd:kernel"] += 1
            return tuple(N._ln_fwd(x2d, w, b, eps))
        stats["ln_fwd:fallback"] += 1
        xf = x2d.astype(jnp.float32)
        mean = jnp.mean(xf, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (xf - mean) * rstd
        y = (xhat * w.astype(jnp.float32)
             + b.astype(jnp.float32)).astype(x2d.dtype)
        n = x2d.shape[0]
        return (y, jnp.broadcast_to(mean, (n, LANES)),
                jnp.broadcast_to(rstd, (n, LANES)))

    def plan(mesh, args):
        r, _, ok = _rows_plan(mesh, args[0], N._BLOCK_ROWS)
        return ((P(r, None), P(None), P(None)),
                (P(r, None), P(r, None), P(r, None)),
                ok)

    return _build(body, plan)


@functools.lru_cache(maxsize=None)
def ln_bwd(eps: float):
    N = _mod("norm")

    def body(ctx, x2d, w, mean, rstd, g):
        raxes, use_kernel = ctx
        if use_kernel:
            stats["ln_bwd:kernel"] += 1
            dx, dw, db = N._ln_bwd_call(x2d, w, mean, rstd, g)
        else:
            stats["ln_bwd:fallback"] += 1
            xf = x2d.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            wf = w.astype(jnp.float32)
            mu, rs = mean[:, :1], rstd[:, :1]
            xhat = (xf - mu) * rs
            wg = gf * wf
            c1 = jnp.mean(wg, axis=1, keepdims=True)
            c2 = jnp.mean(wg * xhat, axis=1, keepdims=True)
            dx = (rs * (wg - c1 - xhat * c2)).astype(x2d.dtype)
            dw = jnp.sum(gf * xhat, axis=0)
            db = jnp.sum(gf, axis=0)
        if raxes:
            dw = jax.lax.psum(dw, raxes)
            db = jax.lax.psum(db, raxes)
        return dx, dw, db

    def plan(mesh, args):
        r, raxes, ok = _rows_plan(mesh, args[0], N._BLOCK_ROWS)
        return ((P(r, None), P(None), P(r, None), P(r, None), P(r, None)),
                (P(r, None), P(None), P(None)),
                (raxes, ok))

    return _build(body, plan)


# ---------------------------------------------------------------------------
# softmax cross-entropy — [n, v] units
# ---------------------------------------------------------------------------

def _xent_plan(mesh, x_arg):
    """Rows over the data axes, vocab over tp (Megatron lm-head) when
    each vocab shard still tiles. Returns (row entry, vocab entry, vocab
    axes, use_kernel)."""
    X = _mod("softmax_xent")

    n, v = x_arg.shape
    used: set = set()
    r = _valid_dim(mesh, _data(mesh), n, used)
    vv = _valid_dim(mesh, _live(mesh, "tp"), v, used, multiple=X._BLOCK_V)
    ok = _rows_aligned(n // _size(mesh, r), X._BLOCK_N)
    return r, vv, _axes(vv), ok


@functools.lru_cache(maxsize=None)
def xent_lse():
    """Row log-sum-exp over [n, v] (lane-replicated [n, 128] out). With
    the vocab dim sharded (Megatron-style tp lm-head) each shard
    computes its local lse and the shards combine with the standard
    max/psum log-sum-exp merge over the vocab axes."""
    X = _mod("softmax_xent")

    def body(ctx, logits):
        vaxes, use_kernel = ctx
        if use_kernel and logits.shape[1] % X._BLOCK_V == 0:
            stats["xent_lse:kernel"] += 1
            lse = X._lse_call(logits)
        else:
            stats["xent_lse:fallback"] += 1
            red = jax.nn.logsumexp(logits.astype(jnp.float32), axis=1,
                                   keepdims=True)
            lse = jnp.broadcast_to(red, (logits.shape[0], LANES))
        if vaxes:
            m = jax.lax.pmax(lse, vaxes)
            lse = m + jnp.log(jax.lax.psum(jnp.exp(lse - m), vaxes))
        return lse

    def plan(mesh, args):
        r, vv, vaxes, ok = _xent_plan(mesh, args[0])
        return (P(r, vv),), P(r, None), (vaxes, ok)

    return _build(body, plan)


@functools.lru_cache(maxsize=None)
def xent_dx():
    """softmax·g over [n, v] given lane-replicated lse/g — elementwise in
    v, so both n and v shard cleanly."""
    X = _mod("softmax_xent")

    def body(use_kernel, logits, lse_b, g_b):
        if use_kernel and logits.shape[1] % X._BLOCK_V == 0:
            stats["xent_dx:kernel"] += 1
            return X._dx_call(logits, lse_b, g_b)
        stats["xent_dx:fallback"] += 1
        return (jnp.exp(logits.astype(jnp.float32) - lse_b[:, :1])
                * g_b[:, :1]).astype(logits.dtype)

    def plan(mesh, args):
        r, vv, _, ok = _xent_plan(mesh, args[0])
        return (P(r, vv), P(r, None), P(r, None)), P(r, vv), ok

    return _build(body, plan)


# ---------------------------------------------------------------------------
# fused linear ⊗ cross-entropy — (h [n, e], w [e, v]) units
# ---------------------------------------------------------------------------

def _flce_plan(mesh, h_arg, w_arg):
    """Rows over the data axes; vocab over tp (Megatron lm-head); the
    contracted e dim replicated (a ZeRO-sharded weight is all-gathered
    at the boundary, exactly as the dense matmul path would). ctx =
    (vaxes, vsizes, raxes, use_kernel)."""
    X = _mod("linear_xent")
    n, e = h_arg.shape
    v = w_arg.shape[1]
    used: set = set()
    r = _valid_dim(mesh, _data(mesh), n, used)
    vv = _valid_dim(mesh, _live(mesh, "tp"), v, used)
    n_local = n // _size(mesh, r)
    v_local = v // _size(mesh, vv)
    itemsize = jnp.dtype(w_arg.dtype).itemsize
    ok = (e % LANES == 0 and n_local % 8 == 0
          and n_local % X._pick_bn(n_local, e) == 0
          and X._pick_bv(e, v_local, itemsize) is not None
          and X._pick_bv(e, v_local, itemsize, for_dw=True) is not None)
    vsizes = tuple(mesh.shape[a] for a in _axes(vv))
    return r, vv, (_axes(vv), vsizes, _axes(r), ok)


def _flce_shift(lab_b, vaxes, vsizes, v_local):
    """Global→local label shift for a vocab-sharded weight: subtract this
    shard's column offset (row-major over the vocab axes). Out-of-range
    rows (another shard's labels, or an ignore_index) select nothing."""
    if not vaxes:
        return lab_b
    idx = jnp.int32(0)
    for a, s in zip(vaxes, vsizes):
        idx = idx * s + jax.lax.axis_index(a)
    return lab_b - idx * v_local


def _flce_fallback_fwd(h, w, lab_local):
    logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
    n, v_local = logits.shape
    lse = jax.nn.logsumexp(logits, axis=1, keepdims=True)
    lab = lab_local[:, :1]
    in_range = (lab >= 0) & (lab < v_local)
    safe = jnp.clip(lab, 0, v_local - 1)
    sel = jnp.take_along_axis(logits, safe, axis=1)
    sel = jnp.where(in_range, sel, 0.0)
    return (jnp.broadcast_to(lse, (n, LANES)),
            jnp.broadcast_to(sel, (n, LANES)))


def _flce_fallback_dlog(h, w, lab_local, lse_b, g_b):
    logits = jnp.dot(h, w, preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse_b[:, :1])
    v_local = logits.shape[1]
    col = jnp.arange(v_local, dtype=jnp.int32)[None, :]
    onehot = (col == lab_local[:, :1]).astype(jnp.float32)
    return (p - onehot) * g_b[:, :1]


@functools.lru_cache(maxsize=None)
def flce_fwd():
    """(lse [n, 128], sel [n, 128]) from (h, w, lab). A sharded vocab
    combines with the standard max/psum log-sum-exp merge; sel is a psum
    (exactly one shard holds each in-range label)."""
    X = _mod("linear_xent")

    def body(ctx, h, w, lab_b):
        vaxes, vsizes, _, use_kernel = ctx
        lab_local = _flce_shift(lab_b, vaxes, vsizes, w.shape[1])
        if use_kernel:
            stats["flce_fwd:kernel"] += 1
            lse, sel = X._fwd_call(h, w, lab_local)
        else:
            stats["flce_fwd:fallback"] += 1
            lse, sel = _flce_fallback_fwd(h, w, lab_local)
        if vaxes:
            m = jax.lax.pmax(lse, vaxes)
            lse = m + jnp.log(jax.lax.psum(jnp.exp(lse - m), vaxes))
            sel = jax.lax.psum(sel, vaxes)
        return lse, sel

    def plan(mesh, args):
        r, vv, ctx = _flce_plan(mesh, args[0], args[1])
        return ((P(r, None), P(None, vv), P(r, None)),
                (P(r, None), P(r, None)), ctx)

    return _build(body, plan)


@functools.lru_cache(maxsize=None)
def flce_dh():
    """dHidden [n, e]: each vocab shard contributes its tile-recomputed
    ``dlogits @ Wᵀ`` partial; psum over the vocab axes."""
    X = _mod("linear_xent")

    def body(ctx, h, w, lab_b, lse_b, g_b):
        vaxes, vsizes, _, use_kernel = ctx
        lab_local = _flce_shift(lab_b, vaxes, vsizes, w.shape[1])
        if use_kernel:
            stats["flce_dh:kernel"] += 1
            dh = X._dh_call(h, w, lab_local, lse_b, g_b)
        else:
            stats["flce_dh:fallback"] += 1
            dlog = _flce_fallback_dlog(h, w, lab_local, lse_b, g_b)
            dh = jax.lax.dot_general(
                dlog.astype(w.dtype), w, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(h.dtype)
        if vaxes:
            dh = jax.lax.psum(dh, vaxes)
        return dh

    def plan(mesh, args):
        r, vv, ctx = _flce_plan(mesh, args[0], args[1])
        io = (P(r, None), P(None, vv), P(r, None), P(r, None), P(r, None))
        return io, P(r, None), ctx

    return _build(body, plan)


@functools.lru_cache(maxsize=None)
def flce_dw():
    """dW [e, v] (weight dtype): vocab-sharded output; row-sharded
    inputs psum their partials over the row axes (f32 for the combine)."""
    X = _mod("linear_xent")

    def body(ctx, h, w, lab_b, lse_b, g_b):
        vaxes, vsizes, raxes, use_kernel = ctx
        lab_local = _flce_shift(lab_b, vaxes, vsizes, w.shape[1])
        if use_kernel:
            stats["flce_dw:kernel"] += 1
            dw = X._dw_call(h, w, lab_local, lse_b, g_b)
        else:
            stats["flce_dw:fallback"] += 1
            dlog = _flce_fallback_dlog(h, w, lab_local, lse_b, g_b)
            dw = jax.lax.dot_general(
                h, dlog.astype(h.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(w.dtype)
        if raxes:
            dw = jax.lax.psum(dw.astype(jnp.float32),
                              raxes).astype(w.dtype)
        return dw

    def plan(mesh, args):
        r, vv, ctx = _flce_plan(mesh, args[0], args[1])
        io = (P(r, None), P(None, vv), P(r, None), P(r, None), P(r, None))
        return io, P(None, vv), ctx

    return _build(body, plan)


# ---------------------------------------------------------------------------
# rotary embedding — [B, T, H, D] with [T, D/2] tables
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def rope(sign: float):
    R = _mod("rope")

    def body(use_kernel, x, cos, sin):
        if use_kernel:
            stats["rope:kernel"] += 1
            return R._rope_call(x, cos, sin, sign)
        stats["rope:fallback"] += 1
        d2 = x.shape[-1] // 2
        x1, x2 = x[..., :d2], x[..., d2:]
        c = cos[None, :, None, :]
        s = sin[None, :, None, :] * sign
        x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
        return jnp.concatenate(
            [x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(x.dtype)

    def plan(mesh, args):
        B, T, H, D = args[0].shape
        used: set = set()
        b = _valid_dim(mesh, _data(mesh), B, used)
        t = _valid_dim(mesh, _live(mesh, "sp"), T, used)
        h = _valid_dim(mesh, _live(mesh, "tp"), H, used)
        ok = _rows_aligned(T // _size(mesh, t), R._BLOCK_T)
        # the tables shard with the sequence so each shard rotates by its
        # own absolute positions
        return ((P(b, t, h, None), P(t, None), P(t, None)),
                P(b, t, h, None), ok)

    return _build(body, plan)


# ---------------------------------------------------------------------------
# selective scan (Mamba) — [B, T, Ei] with [N, Ei] state matrix
# ---------------------------------------------------------------------------

def _ss_plan(mesh, args):
    """Batch over the data axes, channels (lanes) over tp; time is
    sequential and the state dim lives on sublanes — both replicated.
    A channel sharding must keep each shard lane-tiled (Ei_local % 128),
    else it is dropped (the kernel then runs on the full channel width
    per batch shard)."""
    Bsz, T, Ei = args[0].shape
    used: set = set()
    b = _valid_dim(mesh, _data(mesh), Bsz, used)
    e = _valid_dim(mesh, _live(mesh, "tp"), Ei, used, multiple=LANES)
    return b, e


@functools.lru_cache(maxsize=None)
def selective_scan_fwd(k: int):
    SS = _mod("selective_scan")

    def body(ctx, u, delta, At, B, C, D2):
        stats["selective_scan_fwd:kernel"] += 1
        return tuple(SS._fwd_call(u, delta, At, B, C, D2, k))

    def plan(mesh, args):
        b, e = _ss_plan(mesh, args)
        te = P(b, None, e)
        tn = P(b, None, None)
        arg_specs = (te, te, P(None, e), tn, tn, P(None, e))
        return arg_specs, (te, P(b, None, None, e)), None

    return _build(body, plan)


@functools.lru_cache(maxsize=None)
def selective_scan_bwd(k: int):
    SS = _mod("selective_scan")

    def body(caxes, u, delta, At, B, C, h0, dy):
        stats["selective_scan_bwd:kernel"] += 1
        du, ddt, dB, dC, dA_part = SS._bwd_call(u, delta, At, B, C, h0,
                                                dy, k)
        if caxes:
            # dB/dC reduce over channels; with channels sharded each
            # shard holds a partial sum
            dB = jax.lax.psum(dB, caxes)
            dC = jax.lax.psum(dC, caxes)
        return du, ddt, dB, dC, dA_part

    def plan(mesh, args):
        b, e = _ss_plan(mesh, args)
        te = P(b, None, e)
        tn = P(b, None, None)
        arg_specs = (te, te, P(None, e), tn, tn, P(b, None, None, e), te)
        return arg_specs, (te, te, tn, tn, P(b, None, e)), _axes(e)

    return _build(body, plan)


# ---------------------------------------------------------------------------
# decode attention (serving): shard over batch + kv heads
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def decode_attn(scale: float, quantized: bool):
    """args: (sp [2], q2 [B,Hq,D], kn2 [B,Hkv,D], vn2, kc [L,B,Hkv,S,D],
    vc, [ks [L,B,Hkv,S], vs]). Batch over the data axes, heads over tp
    (whole GQA groups); layer/seq/head_dim and the scalar-prefetch
    vector replicated."""
    DA = _mod("decode_attention")

    def body(ctx, sp, q2, kn2, vn2, *cache):
        stats["decode_attn:kernel"] += 1
        return DA.raw_call(sp, q2, kn2, vn2, *cache, scale=scale)

    def plan(mesh, args):
        B, Hq = args[1].shape[0], args[1].shape[1]
        b, h = _batch_head_plan(mesh, B, Hq, args[2].shape[1])
        qkv = P(b, h, None)
        arg_specs = [P(None), qkv, qkv, qkv,
                     P(None, b, h, None, None), P(None, b, h, None, None)]
        if quantized:
            arg_specs += [P(None, b, h, None), P(None, b, h, None)]
        return tuple(arg_specs), qkv, None

    return _build(body, plan)
