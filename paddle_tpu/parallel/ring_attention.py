"""Long-context sequence parallelism: ring attention and Ulysses.

**Absent from the reference** (SURVEY.md §2.3.8: no sequence/context
parallelism in the snapshot — long sequences were handled only by
recompute + pipeline microbatching). This is the new capability layered on
the same mesh substrate, as the north-star requires.

- **Ring attention** (shard_map + ppermute over ``sp``): Q stays local,
  K/V blocks rotate around the ring; softmax is accumulated online
  (flash-attention style m/l/acc carry), so each chip only ever holds
  O(T/S) keys — memory scales with the ring. KV movement overlaps with
  the block matmuls on ICI neighbors.
- **Ulysses** (all_to_all over ``sp``): resharding trick — attention
  inputs flip from sequence-sharded to head-sharded, run dense local
  attention over the full sequence, flip back. Cheaper comm for moderate
  T, requires heads % sp == 0.

Both compute *exactly* standard attention (tested against the dense
reference).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention", "ring_self_attention",
           "ulysses_self_attention", "global_positions"]


def global_positions(t_local: int, axis: str = "sp"):
    """Absolute sequence positions for a [.., T_local, ..] activation.

    Outside any manual region (or when ``axis`` is absent/automatic) the
    local view IS the global sequence: plain ``arange``. Inside a
    computation that is *manual* over ``axis`` (the pipeline shard_maps
    run manual over {pp, sp} so ring/Ulysses need no nested shard_map —
    Shardy rejects nested manual computations, see
    tests/repros/shardy_nested_manual_sp.py) each shard holds the
    ``axis_index``-th sequence slice, so positions offset by rank —
    RoPE and other position encodings stay globally correct."""
    am = jax.sharding.get_abstract_mesh()
    if axis in am.shape:
        types = dict(zip(am.axis_names, am.axis_types))
        if types[axis] == jax.sharding.AxisType.Manual:
            return lax.axis_index(axis) * t_local + jnp.arange(t_local)
    return jnp.arange(t_local)


def _repeat_kv(q, k, v):
    Hq, Hkv = q.shape[2], k.shape[2]
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def ring_attention(q, k, v, *, axis: str = "sp", causal: bool = True,
                   scale: float | None = None):
    """Blockwise ring attention. Call *inside* shard_map with q/k/v
    sequence-sharded over ``axis``: q [B, Tq/S, H, D] local."""
    k, v = _repeat_kv(q, k, v)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    S = lax.axis_size(axis)
    r = lax.axis_index(axis)
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    qf = q.astype(jnp.float32)
    m0 = jnp.full((B, H, Tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    acc0 = jnp.zeros((B, Tq, H, D), jnp.float32)

    q_pos = (r * Tq + jnp.arange(Tq, dtype=jnp.int32)).astype(jnp.int32)

    def step(carry, i):
        m, l, acc, k_blk, v_blk = carry
        # block currently held originated at rank (r - i) mod S
        src = ((r - i) % S).astype(jnp.int32)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k_blk.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * Tk + jnp.arange(Tk, dtype=jnp.int32)
            mask = k_pos[None, :] <= q_pos[:, None]
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
        blk_max = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        # guard fully-masked rows (exp(-inf - -inf))
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(logits - safe_m[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = (acc * corr.transpose(0, 2, 1)[..., None]
                   + jnp.einsum("bhqk,bkhd->bqhd", p,
                                v_blk.astype(jnp.float32)))
        # rotate kv to the next rank (overlaps with next block's matmul)
        perm = [(j, (j + 1) % S) for j in range(S)]
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return (m_new, l_new, acc_new, k_blk, v_blk), None

    (m, l, acc, _, _), _ = lax.scan(step, (m0, l0, acc0, k, v),
                                    jnp.arange(S, dtype=jnp.int32))
    l = jnp.maximum(l, 1e-20)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, *, axis: str = "sp", causal: bool = True,
                      scale: float | None = None):
    """Ulysses attention. Call *inside* shard_map with q/k/v
    sequence-sharded over ``axis``; requires heads % axis_size == 0."""
    from paddle_tpu.nn.functional import scaled_dot_product_attention

    k, v = _repeat_kv(q, k, v)
    # seq-sharded [B, T/S, H, D] -> head-sharded [B, T, H/S, D]
    def fwd(x):
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    def bwd(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    # Inside the fully-manual shard_map the dispatch gate resolves to the
    # *raw* kernel on the local [B, T, H/S, D] shapes (mode "raw"), so the
    # head-sharded local attention runs the flash kernel on TPU; under a
    # partially-manual context it stays on the dense path.
    out = scaled_dot_product_attention(fwd(q), fwd(k), fwd(v),
                                       causal=causal, scale=scale)
    return bwd(out)


def _self_attention_wrapper(inner, q, k, v, mesh, axis, causal, scale):
    # Composition with other manual collectives (the pipeline's shard_map
    # over "pp"): inside a manual computation the ambient mesh is
    # *abstract* and must be the one handed to the nested shard_map; and
    # if ``axis`` itself is already manual (the pipeline runs stages
    # sequence-sharded), there is nothing to wrap — call the ring body
    # directly in the per-device view.
    am = jax.sharding.get_abstract_mesh()
    if am is not None and am.shape and axis in am.shape:
        types = dict(zip(am.axis_names, am.axis_types))
        if types[axis] == jax.sharding.AxisType.Manual:
            return inner(q, k, v, axis=axis, causal=causal, scale=scale)
        if any(t == jax.sharding.AxisType.Manual for t in am.axis_types):
            mesh = am  # nested shard_map must reference the context mesh
    spec = P(None, axis, None, None)
    f = jax.shard_map(
        partial(inner, axis=axis, causal=causal, scale=scale),
        mesh=mesh, axis_names={axis},
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return f(q, k, v)


def ring_self_attention(q, k, v, mesh=None, *, axis: str = "sp",
                        causal: bool = True, scale: float | None = None):
    """Global-view entry: q/k/v [B, T, H, D] (any current sharding; XLA
    reshards to sequence-sharded), runs the ring inside shard_map."""
    if mesh is None:
        from paddle_tpu.parallel.mesh import get_mesh
        mesh = get_mesh()
    return _self_attention_wrapper(ring_attention, q, k, v, mesh, axis,
                                   causal, scale)


def ulysses_self_attention(q, k, v, mesh=None, *, axis: str = "sp",
                           causal: bool = True, scale: float | None = None):
    if mesh is None:
        from paddle_tpu.parallel.mesh import get_mesh
        mesh = get_mesh()
    return _self_attention_wrapper(ulysses_attention, q, k, v, mesh, axis,
                                   causal, scale)
