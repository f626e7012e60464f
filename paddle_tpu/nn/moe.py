"""Mixture-of-experts with expert parallelism over the ``ep`` mesh axis.

New capability beyond the reference snapshot (SURVEY.md §2.3.8 lists
MoE/expert parallelism as absent upstream), built on the same mesh
substrate as the other strategies.

TPU-native design — two dispatch modes sharing one routing core:

- ``einsum`` (GShard dense dispatch): token→expert routing expressed as
  two einsums against a one-hot dispatch tensor, so every shape is
  static and the dispatch/combine contractions lower onto the MXU.
  Experts are stacked weights with a leading expert axis sharded
  ``P("ep", ...)``; a sharding constraint on the ``[E, C, H]`` expert
  buffers makes XLA insert the token all_to_all over ``ep`` — the
  hand-written NCCL AllToAll of GPU MoE frameworks, derived by the
  partitioner instead. This is the mode that makes expert parallelism
  work, but the dispatch/combine contractions cost ``O(N²·k·cf·H)``
  matmul FLOPs — at large per-device token counts they rival the expert
  matmuls themselves — and materialize two ``[N, E, C]`` one-hots.
- ``gather`` (index dispatch): the same routing decisions expressed as
  a row-index inverse map — a tiny int scatter builds ``slot→token``,
  a row gather packs ``[E, C, H]`` expert inputs, and combine is a
  k-row gather + weighted sum. Shapes stay static (capacity padding is
  unchanged); the quadratic one-hot contractions and both ``[N, E, C]``
  tensors disappear, replaced by bandwidth-bound row moves (the
  embedding-lookup pattern XLA handles natively). This is the fast path
  when experts are local (no ``ep`` axis, or ep size 1).

- ``gather_grouped`` (opt-in, for expert parallelism at scale): tokens
  reshaped into G batch-shard groups, routing vmapped per group (the
  position cumsum becomes group-local — under a dp-sharded batch the
  global-N cumsum of the other modes forces cross-shard prefix sums),
  each group gather-packs a ``[E, C/G, H]`` buffer, and one transpose
  with an ``ep`` sharding constraint is the dp→ep all_to_all, derived
  by the partitioner exactly like the einsum mode's — but with no
  ``[N, E, C]`` one-hots at any point. Capacity is per group (each
  group owns a C/G quota per expert — GShard's real grouping
  semantics), so drop behavior differs from the global-capacity modes
  when load is uneven across groups; with ample capacity all three
  modes agree exactly.

``dispatch_mode="auto"`` picks ``gather`` unless the ambient mesh has a
real ``ep`` axis (where a derived all_to_all is load-bearing; the
global-capacity einsum form keeps the long-standing parity contract).
``einsum``/``gather`` produce identical routing (same capacity/drop
semantics, same gates) — parity-tested in ``test_moe.py``, as is the
ample-capacity three-way agreement.

Load-balancing auxiliary loss follows Switch/GShard:
``aux = E * sum_e(frac_tokens_e * mean_gate_e)``.

Routing rule (``route``) is a parameter of the one expert layer:
``"softmax"`` (Switch/Mixtral/OLMoE: softmax over all experts,
sequential top-k, gates not renormalised) or ``"sigmoid_group"``
(DeepSeek-V3: sigmoid scores, a selection bias that moves picks and not
gates, group-limited top-k, gates normalised over the chosen and
scaled; no aux loss). A layer may also carry one shared expert
(``shared_size``) that every token passes through, and may be told
which experts it holds (``held = (first, count)``): it then routes over
all ``num_experts``, allocates and computes only its own, and returns
their part of the result — one chip's share of an expert-parallel
layer, without the exchange. The held form is dropless by
construction: its capacity is the chunk's token count, at which a
pick's slot is its token's own index, so the expert buffer is the token
block itself and the gate matrix (zero where an expert was not picked)
is the combine. On that form the layer may also read its routing
logits from another tensor than the one its experts multiply
(``route_from=``: SmallThinker routes from the block's input, ahead of
the norm and of attention), divide the softmax gates at the picks by
their sum (``norm_topk``), scale them after that (``routed_scale``, on
the softmax route as on the sigmoid one: Laguna's 2.5), and gate with
relu (``act="relu"``: ReGLU).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import rng
from paddle_tpu.core.module import Module
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.initializer import Normal

__all__ = ["MoEMLP", "top_k_routing", "top_k_routing_compact",
           "sigmoid_group_picks"]


def _constrain(x, spec: P):
    """Apply a sharding constraint against the ambient mesh, if one is
    set and carries the named axes (no-op otherwise — single-chip runs
    and unit tests don't build a mesh)."""
    from jax.sharding import NamedSharding
    from paddle_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return x
    if any(ax not in mesh.shape for axes in spec if axes
           for ax in (axes if isinstance(axes, tuple) else (axes,))):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _route(logits, k: int, capacity: int):
    """Shared routing core: softmax → sequential top-k picks with
    per-expert slot assignment under capacity. Returns
    ``(probs, rounds, aux_loss)`` where each round is a tuple of [N]
    arrays ``(expert_idx, slot, keep, gate)`` — ``gate`` already zeroed
    for dropped (over-capacity) picks."""
    n, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    rounds = []
    masked = probs
    # claimed[e] tracking via cumulative one-hot counts across the k picks
    prior = jnp.zeros((n, e), jnp.int32)
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)                     # [N]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)      # [N, E]
        # position of each token within its chosen expert's buffer:
        # tokens earlier in the batch claim earlier slots (cumsum), plus
        # slots already used by previous routing rounds
        pos = (jnp.cumsum(onehot, axis=0) - 1) + prior.sum(0)  # [N, E]
        prior = prior + onehot
        pos_t = jnp.sum(pos * onehot, axis=-1)                # [N]
        keep = pos_t < capacity
        gate = jnp.sum(probs * onehot, axis=-1) * keep        # [N]
        rounds.append((idx, pos_t, keep, gate))
        masked = masked * (1 - onehot)

    return probs, rounds, _switch_aux_loss(probs)


def _switch_aux_loss(probs):
    """Switch aux loss: fraction of tokens per expert × mean router
    prob, over whatever token population ``probs`` covers."""
    e = probs.shape[-1]
    frac = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=probs.dtype), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return e * jnp.sum(frac * mean_prob)


def top_k_routing(logits, k: int, capacity: int):
    """Route tokens to top-k experts under a per-expert capacity.

    Args:
      logits: [N, E] router scores.
      k: experts per token.
      capacity: max tokens an expert accepts (overflow tokens drop —
        Switch-transformer semantics; the residual path carries them).

    Returns:
      dispatch: [N, E, C] one-hot dispatch tensor.
      combine:  [N, E, C] gate-weighted combine tensor.
      aux_loss: scalar load-balancing loss.
    """
    n, e = logits.shape
    probs, rounds, aux_loss = _route(logits, k, capacity)
    dispatch = jnp.zeros((n, e, capacity), probs.dtype)
    combine = jnp.zeros((n, e, capacity), probs.dtype)
    for idx, pos_t, keep, gate in rounds:
        onehot = jax.nn.one_hot(idx, e, dtype=probs.dtype)    # [N, E]
        oh_pos = jax.nn.one_hot(pos_t, capacity,
                                dtype=probs.dtype)            # [N, C]
        d = (onehot[:, :, None] * oh_pos[:, None, :]
             * keep.astype(probs.dtype)[:, None, None])
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
    return dispatch, combine, aux_loss


def top_k_routing_compact(logits, k: int, capacity: int):
    """Index form of :func:`top_k_routing` — the same routing decisions
    without the [N, E, C] one-hots.

    Returns ``(expert, slot, keep, gate, aux_loss)``, each [N, k]:
    ``expert[n, j]`` is the j-th pick's expert, ``slot[n, j]`` its
    position in that expert's capacity buffer (may be ≥ capacity when
    dropped), ``keep`` the in-capacity mask, and ``gate`` the softmax
    gate weight (zero where dropped)."""
    _, rounds, aux_loss = _route(logits, k, capacity)
    expert = jnp.stack([r[0] for r in rounds], axis=1)
    slot = jnp.stack([r[1] for r in rounds], axis=1)
    keep = jnp.stack([r[2] for r in rounds], axis=1)
    gate = jnp.stack([r[3] for r in rounds], axis=1)
    return expert, slot, keep, gate, aux_loss


def softmax_picks(logits, k: int):
    """The softmax rule's picks with no capacity: ``(expert, gate)``
    [N, k] — the k largest probabilities in order, gates as they are."""
    gate, expert = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return expert, gate


def sigmoid_group_picks(logits, bias, k: int, n_group: int,
                        topk_group: int, scale: float):
    """DeepSeek-V3 routing. Scores ``s = sigmoid(logits)``; selection
    scores ``s' = s + bias``; experts lie in ``n_group`` groups of
    consecutive ids, a group scores the sum of its two largest ``s'``,
    the ``topk_group`` best groups stay and the ``k`` largest ``s'``
    among their experts are picked. Gates are ``s`` (not ``s'``) at the
    picks, divided by their sum and multiplied by ``scale``. Returns
    ``(expert, gate)`` [N, k], float32 gates."""
    n, e = logits.shape
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    sel = s + bias.astype(jnp.float32)
    per = sel.reshape(n, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)   # [N, G]
    _, best = jax.lax.top_k(group_score, topk_group)
    keep = jnp.sum(jax.nn.one_hot(best, n_group, dtype=jnp.int32),
                   axis=1) > 0                                 # [N, G]
    sel = jnp.where(jnp.repeat(keep, e // n_group, axis=1), sel,
                    -jnp.inf)
    _, expert = jax.lax.top_k(sel, k)
    gate = jnp.take_along_axis(s, expert, axis=1)
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) * scale
    return expert, gate


class MoEMLP(Module):
    """Top-k routed SwiGLU expert MLPs (drop-in for a dense LlamaMLP).

    ``__call__`` returns ``(out, aux_loss)`` — the caller folds the aux
    loss (scaled by ``aux_weight``) into the training loss.
    """

    def __init__(self, hidden_size: int, intermediate_size: int,
                 num_experts: int, *, top_k: int = 2,
                 capacity_factor: float = 1.25, init_std: float = 0.02,
                 num_layers: int = 1, dtype=jnp.float32,
                 dispatch_mode: str = "auto", route: str = "softmax",
                 n_group: int = 1, topk_group: int = 1,
                 routed_scale: float = 1.0, shared_size: int = 0,
                 held: tuple[int, int] | None = None,
                 norm_topk: bool = False, act: str = "silu", key=None):
        if dispatch_mode not in ("auto", "einsum", "gather",
                                 "gather_grouped"):
            raise ValueError(
                f"dispatch_mode must be auto|einsum|gather|gather_grouped,"
                f" got {dispatch_mode!r}")
        if route not in ("softmax", "sigmoid_group"):
            raise ValueError(
                f"route must be softmax|sigmoid_group, got {route!r}")
        E, H, I_ = num_experts, hidden_size, intermediate_size
        if route == "sigmoid_group":
            if E % n_group or not 1 <= topk_group <= n_group:
                raise ValueError(
                    f"sigmoid_group routing: {n_group} groups must divide "
                    f"{E} experts and topk_group {topk_group} lie in "
                    f"1..{n_group}")
        if act not in ("silu", "relu"):
            raise ValueError(f"act must be silu|relu, got {act!r}")
        if held is None and (route == "sigmoid_group" or shared_size
                             or norm_topk or act != "silu"):
            # the capacity dispatch forms are the plain softmax layer's
            held = (0, E)
        if held is not None:
            first, count = (int(v) for v in held)
            if not (0 <= first and 0 < count and first + count <= E):
                raise ValueError(f"held {held!r} is not a range of the "
                                 f"{E} experts")
            held = (first, count)
        keys = rng.split_key(key, 4)
        n_held = E if held is None else held[1]
        init = Normal(0.0, init_std)
        down_init = Normal(0.0, init_std / math.sqrt(2 * num_layers))
        # router replicated (tiny); experts stacked on a leading ep axis.
        # A layer that holds a share allocates its own experts only; the
        # router keeps its full width.
        self.router = init(keys[0], (H, E), jnp.float32)
        self.w_gate = init(keys[1], (n_held, H, I_), dtype)
        self.w_up = init(keys[2], (n_held, H, I_), dtype)
        self.w_down = down_init(keys[3], (n_held, I_, H), dtype)
        pspecs = [
            ("router", P()),
            ("w_gate", P("ep", "fsdp", "tp")),
            ("w_up", P("ep", "fsdp", "tp")),
            ("w_down", P("ep", "tp", "fsdp")),
        ]
        if route == "sigmoid_group":
            # e_score_correction_bias: moves picks, never gates
            self.select_bias = jnp.zeros((E,), jnp.float32)
            pspecs.append(("select_bias", P()))
        if shared_size:
            ks = rng.split_key(keys[0], 3)
            S_ = int(shared_size)
            self.shared_gate = init(ks[0], (H, S_), dtype)
            self.shared_up = init(ks[1], (H, S_), dtype)
            self.shared_down = down_init(ks[2], (S_, H), dtype)
            pspecs += [("shared_gate", P("fsdp", "tp")),
                       ("shared_up", P("fsdp", "tp")),
                       ("shared_down", P("tp", "fsdp"))]
        self._pspecs = tuple(pspecs)
        self.num_experts = E
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.dispatch_mode = dispatch_mode
        if held is not None:
            # static fields only where they are used: the plain softmax
            # layer keeps the tree structure it had
            from paddle_tpu.nn.stateful import new_uid
            self.route = route
            self.n_group, self.topk_group = int(n_group), int(topk_group)
            self.routed_scale = float(routed_scale)
            self.held = held
            self._uid = new_uid()
            if norm_topk:
                self.norm_topk = True
            if act != "silu":
                self.act = act

    def capacity(self, n_tokens: int) -> int:
        c = int(math.ceil(n_tokens * self.top_k * self.capacity_factor
                          / self.num_experts))
        return max(c, self.top_k)

    def _resolved_mode(self) -> str:
        """Resolve ``auto`` at trace time against the ambient mesh: the
        einsum form's derived all_to_all is load-bearing only when a
        real ``ep`` axis exists; everywhere else the quadratic one-hot
        contractions are pure overhead and ``gather`` wins."""
        if self.dispatch_mode != "auto":
            return self.dispatch_mode
        from paddle_tpu.parallel.mesh import current_mesh
        mesh = current_mesh()
        if mesh is not None and dict(mesh.shape).get("ep", 1) > 1:
            return "einsum"
        return "gather"

    @jax.named_scope("moe/experts")
    def _experts(self, expert_in):
        sg = getattr(self, "w_gate_scale", None)
        if sg is not None:
            # weight-only int8 experts (quant.quantize_weights_int8):
            # the einsum rhs is a bare convert(int8) that XLA fuses into
            # the dot's operand stream; the per-(expert, out-channel)
            # scale applies after the contraction — x @ (q·s) == (x @ q)·s
            dt = expert_in.dtype
            gate = jnp.einsum("ech,ehi->eci", expert_in,
                              self.w_gate.astype(dt)) \
                * sg.astype(dt)[:, None, :]
            up = jnp.einsum("ech,ehi->eci", expert_in,
                            self.w_up.astype(dt)) \
                * self.w_up_scale.astype(dt)[:, None, :]
            act = F.swiglu(up, gate)
            return jnp.einsum("eci,eih->ech", act,
                              self.w_down.astype(dt)) \
                * self.w_down_scale.astype(dt)[:, None, :]
        gate = jnp.einsum("ech,ehi->eci", expert_in, self.w_gate)
        up = jnp.einsum("ech,ehi->eci", expert_in, self.w_up)
        if getattr(self, "act", "silu") == "relu":
            act = jax.nn.relu(gate) * up
        else:
            act = F.swiglu(up, gate)
        return jnp.einsum("eci,eih->ech", act, self.w_down)

    def __call__(self, x, route_from=None):
        """``route_from`` [b, t, h] (the held form only): the tensor
        the router reads, where it is not the experts' input ``x``."""
        b, t, h = x.shape
        n = b * t
        tokens = x.reshape(n, h)
        cap = self.capacity(n)

        # router in fp32 for stable softmax (standard MoE practice).
        # The moe/* scopes name the block's four stages in the device
        # trace, which otherwise shows only fusions.
        if getattr(self, "held", None) is not None:
            out, aux = self._call_held(
                tokens, b, t,
                None if route_from is None else route_from.reshape(n, h))
            if hasattr(self, "shared_gate"):
                with jax.named_scope("moe/shared"):
                    out = out + (F.swiglu(tokens @ self.shared_up,
                                          tokens @ self.shared_gate)
                                 @ self.shared_down)
            return out.reshape(b, t, h), aux
        if route_from is not None:
            raise ValueError("route_from= is the held (dropless) form's: "
                             "give the layer held=(0, num_experts)")

        with jax.named_scope("moe/route"):
            logits = tokens.astype(jnp.float32) @ self.router

        mode = self._resolved_mode()
        if mode == "gather":
            out, aux = self._call_gather(tokens, logits, n, h, cap)
        elif mode == "gather_grouped":
            out, aux = self._call_gather_grouped(tokens, logits, n, h)
        elif mode == "einsum":
            out, aux = self._call_einsum(tokens, logits, n, h, cap)
        else:
            raise ValueError(f"unknown dispatch_mode {mode!r}")
        return out.reshape(b, t, h), aux.astype(jnp.float32)

    def _call_held(self, tokens, b, t, route_tokens=None):
        """The dropless form of a layer that holds experts ``[first,
        first + count)``: route every token over all experts, run the
        held ones on the whole token block (capacity = token count, slot
        = token index) and combine with the gate matrix, which is zero
        wherever a held expert was not picked. What the experts held
        elsewhere would add is left out. Records, for whoever sums over
        live positions, each token's picks and those that fell here."""
        from paddle_tpu.nn.stateful import record_count

        first, count = self.held
        with jax.named_scope("moe/route"):
            # float32 throughout: a pick decided in bf16 is another pick
            logits = jnp.matmul(
                (tokens if route_tokens is None
                 else route_tokens).astype(jnp.float32), self.router,
                precision=jax.lax.Precision.HIGHEST)
            if self.route == "sigmoid_group":
                expert, gate = sigmoid_group_picks(
                    logits, self.select_bias, self.top_k, self.n_group,
                    self.topk_group, self.routed_scale)
            else:
                expert, gate = softmax_picks(logits, self.top_k)
                if getattr(self, "norm_topk", False):
                    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
                if self.routed_scale != 1.0:
                    gate = gate * self.routed_scale
            # one_hot of an id outside [0, count) is a zero row
            here = jax.nn.one_hot(expert - first, count, dtype=gate.dtype)
            gates = jnp.einsum("nk,nkc->nc", gate, here)       # [N, held]
            record_count(
                self._uid,
                moe_picks=jnp.full((b, t), self.top_k, jnp.int32),
                moe_picks_held=jnp.sum(here, axis=(1, 2)).astype(
                    jnp.int32).reshape(b, t))
        with jax.named_scope("moe/dispatch"):
            expert_in = jnp.broadcast_to(tokens[None],
                                         (count,) + tokens.shape)
        expert_out = self._experts(expert_in)                  # [held, N, H]
        with jax.named_scope("moe/combine"):
            out = jnp.einsum("cnh,nc->nh", expert_out,
                             gates.astype(tokens.dtype))
        return out, jnp.zeros((), jnp.float32)

    def _call_einsum(self, tokens, logits, n, h, cap):
        with jax.named_scope("moe/route"):
            dispatch, combine, aux = top_k_routing(logits, self.top_k, cap)
            dispatch = dispatch.astype(tokens.dtype)
            combine = combine.astype(tokens.dtype)

        # dispatch: [N,H] x [N,E,C] -> [E,C,H]; the sharding constraint
        # makes the XLA partitioner materialize the ep all_to_all here
        with jax.named_scope("moe/dispatch"):
            expert_in = jnp.einsum("nh,nec->ech", tokens, dispatch)
            expert_in = _constrain(expert_in, P("ep", None, None))

        expert_out = self._experts(expert_in)
        expert_out = _constrain(expert_out, P("ep", None, None))

        # combine (the return all_to_all): [E,C,H] x [N,E,C] -> [N,H]
        with jax.named_scope("moe/combine"):
            out = jnp.einsum("ech,nec->nh", expert_out, combine)
        return out, aux

    def _call_gather(self, tokens, logits, n, h, cap):
        e, k = self.num_experts, self.top_k
        with jax.named_scope("moe/route"):
            expert, slot, keep, gate, aux = top_k_routing_compact(
                logits, k, cap)

        with jax.named_scope("moe/dispatch"):
            # flat destination slot per (token, pick); dropped picks land in
            # an out-of-bounds trash slot (served by fill-mode gathers below)
            dest = jnp.where(keep, expert * cap + slot, e * cap)      # [N, k]
            # inverse map slot→token: a tiny int scatter (destinations are
            # unique by construction except the shared trash slot); the
            # out-of-bounds sentinel n marks unfilled slots
            src = jnp.full((e * cap + 1,), n, jnp.int32)
            tok_idx = jnp.broadcast_to(
                jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
            src = src.at[dest.reshape(-1)].set(tok_idx.reshape(-1))

            # pack expert inputs with one row gather (embedding-lookup
            # pattern; backward is the scatter-add of embedding grads).
            # mode="fill" zero-fills the sentinel rows without materializing
            # a padded copy of the token buffer, and its transpose drops the
            # out-of-bounds cotangents
            expert_in = jnp.take(tokens, src[:e * cap], axis=0,
                                 mode="fill", fill_value=0).reshape(e, cap, h)
            expert_in = _constrain(expert_in, P("ep", None, None))

        expert_out = self._experts(expert_in)
        expert_out = _constrain(expert_out, P("ep", None, None))

        with jax.named_scope("moe/combine"):
            # combine: k row gathers + gate-weighted sum (the trash slot is
            # out of bounds → zero-filled, and its gate is already zero)
            picked = jnp.take(expert_out.reshape(e * cap, h), dest.reshape(-1),
                              axis=0, mode="fill",
                              fill_value=0).reshape(n, k, h)
            out = jnp.sum(picked * gate.astype(tokens.dtype)[..., None],
                          axis=1)
        return out, aux

    def _groups(self, n: int) -> int:
        """Group count for gather_grouped: the mesh's batch-sharding
        degree (dp·fsdp), so each group is one data shard and the
        vmapped routing never crosses shards. Falls back toward 1 when
        the token count doesn't divide."""
        from paddle_tpu.parallel.mesh import BATCH_AXES, current_mesh
        mesh = current_mesh()
        g = 1
        if mesh is not None:
            shape = dict(mesh.shape)
            for ax in BATCH_AXES:
                g *= shape.get(ax, 1)
        # grouping is only valid when the token count splits EXACTLY
        # into the batch shards: a partial group count (any divisor
        # < g) would break the P(BATCH_AXES, ...) constraint on the
        # [G, E, Cg, H] buffers (G must be divisible by the dp·fsdp
        # shard product) — fall back to one group (no grouping) instead
        return g if g > 0 and n % g == 0 else 1

    def _call_gather_grouped(self, tokens, logits, n, h):
        """Per-group gather dispatch for expert parallelism: G groups of
        n/G tokens each own a capacity(n/G) quota per expert. The
        [G, E, Cg, H] ↔ [E, G, Cg, H] transposes under the dp/ep
        sharding constraints ARE the token all_to_all, derived by the
        partitioner — same collective role as the einsum mode's, with
        no [N, E, C] one-hots anywhere."""
        e, k = self.num_experts, self.top_k
        g = self._groups(n)
        ng = n // g
        cg = self.capacity(ng)
        t_g = tokens.reshape(g, ng, h)
        l_g = logits.reshape(g, ng, e)

        with jax.named_scope("moe/route"):
            expert, slot, keep, gate, _ = jax.vmap(
                lambda lg: top_k_routing_compact(lg, k, cg))(l_g)
            # aux stays GLOBAL (same population as the other modes) — the
            # grouping only changes capacity quotas, not the balance target
            aux = _switch_aux_loss(jax.nn.softmax(logits, axis=-1))

        with jax.named_scope("moe/dispatch"):
            dest = jnp.where(keep, expert * cg + slot, e * cg)    # [G, ng, k]
            tok_idx = jnp.broadcast_to(
                jnp.arange(ng, dtype=jnp.int32)[None, :, None], (g, ng, k))
            src = jnp.full((g, e * cg + 1), ng, jnp.int32)
            src = jax.vmap(lambda s, d, t: s.at[d.reshape(-1)]
                           .set(t.reshape(-1)))(src, dest, tok_idx)

            packed = jax.vmap(lambda tg, sg: jnp.take(
                tg, sg[:e * cg], axis=0, mode="fill", fill_value=0))(t_g, src)
            from paddle_tpu.parallel.mesh import BATCH_AXES
            packed = packed.reshape(g, e, cg, h)
            # double-sharded staging block: each (batch-shard, ep) device
            # holds its (group, expert-shard) tile — the constraint pair
            # makes the partitioner emit the direct batch→ep exchange. The
            # group axis must name ALL batch axes (groups come from
            # dp·fsdp), or an fsdp-sharded batch gets gathered whole
            packed = _constrain(packed, P(BATCH_AXES, "ep", None, None))
            expert_in = packed.transpose(1, 0, 2, 3).reshape(e, g * cg, h)
            expert_in = _constrain(expert_in, P("ep", None, None))

        expert_out = self._experts(expert_in)
        expert_out = _constrain(expert_out, P("ep", None, None))

        with jax.named_scope("moe/combine"):
            back = expert_out.reshape(e, g, cg, h).transpose(1, 0, 2, 3)
            back = _constrain(back, P(BATCH_AXES, "ep", None, None))
            picked = jax.vmap(lambda rows, d: jnp.take(
                rows.reshape(e * cg, h), d.reshape(-1), axis=0, mode="fill",
                fill_value=0))(back, dest)                  # [G, ng*k, H]
            picked = picked.reshape(g, ng, k, h)
            out = jnp.sum(picked * gate.astype(tokens.dtype)[..., None],
                          axis=2)
        return out.reshape(n, h), aux
