"""Fused selective-scan (Mamba SSM recurrence) — Pallas TPU kernel.

The recurrence ``h_t = exp(Δ_t A) ⊙ h_{t-1} + (Δ_t u_t) B_t``,
``y_t = ⟨h_t, C_t⟩ + D u_t`` is the hot loop of the Mamba family
(BASELINE.json north-star "Mamba-2 selective-scan"). The XLA formulation
(``models/mamba.py::selective_scan``, the numerical spec this kernel must
match) is scan-bound: the associative scan materializes [B, T, Ei, N]
discretized operands in HBM and makes log(T) passes over them.

Kernel design: grid (B, Ei/128, T/k) with the chunk dimension sequential
("arbitrary") and the running state h [N, 128] carried in VMEM scratch
across chunk steps. Per chunk the discretization (dA = exp(Δ·A),
dBu = Δu·B — [k, N, 128] tiles, state on sublanes, channels on lanes) is
vectorized VPU work; only the length-k FMA chain is sequential
(``fori_loop``, unrolled). HBM traffic is one read of u/Δ/B/C and one
write of y per token — no [B, T, Ei, N] intermediate ever exists.

Backward: the forward saves only the chunk-boundary states
([B, T/k, N, Ei] — a T/k-fold smaller residual than the full state
trajectory); the backward grid walks chunks in reverse, recomputes the
within-chunk states from the saved boundary state, runs the adjoint
recurrence ``g_t = dy_t C_t + exp(Δ_{t+1} A) g_{t+1}`` with the carry in
scratch, and accumulates the cross-chunk dA reduction in scratch,
writing per-batch partials summed outside.

Reference analogue: the role of Mamba's fused CUDA selective_scan —
structured like the reference's fused-op pattern
(``paddle/fluid/operators/fused/fused_embedding_eltwise_layernorm_op.cu``),
state kept on-chip for the whole sequential dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import _support

_LANES = 128
_DEF_CHUNK = 128


def _chunk(T: int, chunk: int | None) -> int:
    k = min(chunk or _DEF_CHUNK, T)
    return k


def supported(u, delta, A, B, C, D, chunk: int | None = None) -> bool:
    """Shape gate: channels lane-tiled, state sublane-aligned and small
    enough for the [k, N, 128] VMEM working set."""
    if u.ndim != 3 or A.ndim != 2 or B.ndim != 3:
        return False
    Bsz, T, Ei = u.shape
    N = A.shape[1]
    if A.shape[0] != Ei or B.shape != (Bsz, T, N) or C.shape != B.shape:
        return False
    if delta.shape != u.shape or D.shape != (Ei,):
        return False
    if Ei % _LANES:
        return False
    if N % 8 or N > 32:
        return False
    k = _chunk(T, chunk)
    if T % k or k % 8:
        return False
    return all(jnp.dtype(x.dtype) == jnp.float32
               for x in (u, delta, A, B, C, D))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(u_ref, dt_ref, at_ref, b_ref, c_ref, d_ref,
                y_ref, h0_ref, h_ref, *, k, n, nc):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[:] = jnp.zeros_like(h_ref)

    # boundary state entering this chunk (the backward's restart point)
    h0_ref[0, 0] = h_ref[:]

    u = u_ref[0]                                   # [k, 128]
    dt = dt_ref[0]                                 # [k, 128]
    at = at_ref[:]                                 # [N, 128] (= A.T block)
    bc = b_ref[0]                                  # [k, N]
    cc = c_ref[0]                                  # [k, N]

    dA = jnp.exp(dt[:, None, :] * at[None])        # [k, N, 128]
    dBu = (dt * u)[:, None, :] * bc[..., None]     # [k, N, 128]

    # static Python loop: Mosaic TC has no dynamic_slice, and the fully
    # unrolled FMA chain is exactly the schedule we want anyway
    h = h_ref[:]
    hs_list = []
    for i in range(k):
        h = dA[i] * h + dBu[i]
        hs_list.append(h)
    hs = jnp.stack(hs_list)
    h_ref[:] = h

    y = jnp.sum(hs * cc[..., None], axis=1)        # [k, 128]
    y_ref[0] = y + u * d_ref[0]


def _fwd_call(u, delta, At, B, C, D2, k):
    Bsz, T, Ei = u.shape
    N = At.shape[0]
    nc, ne = T // k, Ei // _LANES
    grid = (Bsz, ne, nc)

    ue_spec = pl.BlockSpec((1, k, _LANES), lambda b, e, t: (b, t, e))
    bn_spec = pl.BlockSpec((1, k, N), lambda b, e, t: (b, t, 0))
    y, h0 = pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, n=N, nc=nc),
        grid=grid,
        in_specs=[
            ue_spec,                                            # u
            ue_spec,                                            # delta
            pl.BlockSpec((N, _LANES), lambda b, e, t: (0, e)),  # A.T
            bn_spec,                                            # B
            bn_spec,                                            # C
            pl.BlockSpec((1, _LANES), lambda b, e, t: (0, e)),  # D
        ],
        out_specs=[
            ue_spec,                                            # y
            pl.BlockSpec((1, 1, N, _LANES),
                         lambda b, e, t: (b, t, 0, e)),         # h0/chunk
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
            jax.ShapeDtypeStruct((Bsz, nc, N, Ei), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, _LANES), jnp.float32)],
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_selective_scan_fwd",
    )(u, delta, At, B, C, D2)
    return y, h0


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(u_ref, dt_ref, at_ref, b_ref, c_ref, h0_ref, dy_ref,
                du_ref, ddt_ref, db_ref, dc_ref, dA_ref,
                m_ref, acc_ref, *, k, n, nc):
    it = pl.program_id(2)      # reversed chunk order via the index maps

    @pl.when(it == 0)
    def _init():
        m_ref[:] = jnp.zeros_like(m_ref)      # dA_{t+1}·g_{t+1} message
        acc_ref[:] = jnp.zeros_like(acc_ref)  # ΣA-grad accumulator

    u = u_ref[0]
    dt = dt_ref[0]
    at = at_ref[:]
    bc = b_ref[0]
    cc = c_ref[0]
    dy = dy_ref[0]
    h0 = h0_ref[0, 0]                              # [N, 128]

    dA = jnp.exp(dt[:, None, :] * at[None])        # [k, N, 128]
    dBu = (dt * u)[:, None, :] * bc[..., None]

    # recompute the within-chunk state trajectory from the boundary state
    h = h0
    hp_list = []
    for i in range(k):
        h = dA[i] * h + dBu[i]
        hp_list.append(h)
    hpost = jnp.stack(hp_list)
    # state entering step t: hprev[0] = h0, hprev[t] = hpost[t-1]
    hprev = jnp.concatenate([h0[None], hpost[:-1]], axis=0)

    # reverse adjoint: g_t = dy_t·C_t + m ;  m ← dA_t · g_t
    m = m_ref[:]
    gs_list = [None] * k
    for i in range(k - 1, -1, -1):
        g = cc[i][:, None] * dy[i][None, :] + m
        gs_list[i] = g
        m = dA[i] * g
    gs = jnp.stack(gs_list)
    m_ref[:] = m

    s1 = jnp.sum(gs * bc[..., None], axis=1)       # Σ_n g·B   [k, 128]
    du_ref[0] = dt * s1
    gdh = gs * dA * hprev                          # [k, N, 128]
    ddt_ref[0] = jnp.sum(gdh * at[None], axis=1) + u * s1
    # dB/dC reduce over *all* channels but this cell only sees one lane
    # block — write per-block partials (summed over the ne dim outside;
    # output accumulation across the e grid dim would need contiguous
    # revisiting, which the (b, e, t) grid order does not give)
    db_ref[0, 0] = jnp.sum(gs * (dt * u)[:, None, :], axis=2)   # [k, N]
    dc_ref[0, 0] = jnp.sum(hpost * dy[:, None, :], axis=2)      # [k, N]
    acc_ref[:] += jnp.sum(gdh * dt[:, None, :], axis=0)      # [N, 128]

    @pl.when(it == nc - 1)
    def _finish():
        dA_ref[0] = acc_ref[:]


def _bwd_call(u, delta, At, B, C, h0, dy, k):
    Bsz, T, Ei = u.shape
    N = At.shape[0]
    nc, ne = T // k, Ei // _LANES
    grid = (Bsz, ne, nc)

    # chunk dim walked in reverse
    ue_rev = pl.BlockSpec((1, k, _LANES),
                          lambda b, e, t, nc=nc: (b, nc - 1 - t, e))
    bn_rev = pl.BlockSpec((1, k, N), lambda b, e, t, nc=nc: (b, nc - 1 - t, 0))
    in_specs = [
        ue_rev,                                             # u
        ue_rev,                                             # delta
        pl.BlockSpec((N, _LANES), lambda b, e, t: (0, e)),  # A.T
        bn_rev,                                             # B
        bn_rev,                                             # C
        pl.BlockSpec((1, 1, N, _LANES),
                     lambda b, e, t, nc=nc: (b, nc - 1 - t, 0, e)),
        ue_rev,                                             # dy
    ]
    bn_part = pl.BlockSpec((1, 1, k, N),
                           lambda b, e, t, nc=nc: (b, e, nc - 1 - t, 0))
    du, ddt, dB_blocks, dC_blocks, dA_part = pl.pallas_call(
        functools.partial(_bwd_kernel, k=k, n=N, nc=nc),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            ue_rev,                                             # du
            ue_rev,                                             # ddelta
            bn_part,                                            # dB/e-block
            bn_part,                                            # dC/e-block
            pl.BlockSpec((1, N, _LANES), lambda b, e, t: (b, 0, e)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
            jax.ShapeDtypeStruct(u.shape, jnp.float32),
            jax.ShapeDtypeStruct((Bsz, ne, T, N), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, ne, T, N), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, N, Ei), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, _LANES), jnp.float32),
            pltpu.VMEM((N, _LANES), jnp.float32),
        ],
        compiler_params=_support.compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_support.interpret(),
        name="ptpu_selective_scan_bwd",
    )(u, delta, At, B, C, h0, dy)
    # reduce the per-lane-block partials over the channel-block dim
    return du, ddt, jnp.sum(dB_blocks, axis=1), jnp.sum(dC_blocks, axis=1), \
        dA_part


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------

def _fwd_dispatch(u, delta, At, B, C, D2, k, part):
    if part:
        from paddle_tpu.ops.pallas import _partition
        return _partition.selective_scan_fwd(k)(u, delta, At, B, C, D2)
    return _fwd_call(u, delta, At, B, C, D2, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scan(k, part, u, delta, At, B, C, D2):
    y, _ = _fwd_dispatch(u, delta, At, B, C, D2, k, part)
    return y


def _scan_fwd(k, part, u, delta, At, B, C, D2):
    y, h0 = _fwd_dispatch(u, delta, At, B, C, D2, k, part)
    return y, (u, delta, At, B, C, D2, h0)


def _scan_bwd(k, part, res, dy):
    u, delta, At, B, C, D2, h0 = res
    if part:
        from paddle_tpu.ops.pallas import _partition
        du, ddt, dB, dC, dA_part = _partition.selective_scan_bwd(k)(
            u, delta, At, B, C, h0, dy)
    else:
        du, ddt, dB, dC, dA_part = _bwd_call(u, delta, At, B, C, h0, dy, k)
    # y += u·D terms and the cross-batch reductions stay outside: XLA
    # fuses them into the surrounding elementwise graph
    du = du + dy * D2[0]
    dAt = jnp.sum(dA_part, axis=0)                 # [N, Ei]
    dD = jnp.sum(dy * u, axis=(0, 1))              # [Ei]
    return du, ddt, dAt, dB, dC, dD[None]


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, delta, A, B, C, D, chunk: int | None = None, *,
                   partitioned: bool = False):
    """Fused selective scan; same contract as
    ``models.mamba.selective_scan`` (u:[B,T,Ei] Δ:[B,T,Ei] A:[Ei,N]
    B,C:[B,T,N] D:[Ei] → y:[B,T,Ei]). ``supported(...)`` must hold.
    ``partitioned`` routes through the shard_map unit (batch/channel
    shardable; time sequential, replicated)."""
    k = _chunk(u.shape[1], chunk)
    y = _scan(k, bool(partitioned), u.astype(jnp.float32),
              delta.astype(jnp.float32),
              jnp.transpose(A).astype(jnp.float32),
              B.astype(jnp.float32), C.astype(jnp.float32),
              D.astype(jnp.float32)[None])
    return y
