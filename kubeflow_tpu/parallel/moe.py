"""Mixture-of-Experts with expert parallelism over ICI.

The reference has no MoE/parallelism code (SURVEY.md §2b row "Expert
parallelism (EP/MoE)": "pjit expert axis + ragged all-to-all over ICI").
This module supplies both TPU execution styles:

- `moe_mlp` — the GSPMD path: capacity-based top-k dispatch expressed as
  dense einsums. Under pjit with the experts dim sharded (logical axis
  "experts" → tensor), XLA partitions the expert computation and inserts
  the collectives itself. Zero hand-written communication; best when the
  expert dim is sharded over the same axis as the rest of the layer.

- `moe_mlp_expert_parallel` / `moe_mlp_sharded` — the explicit-EP path:
  `shard_map` over an expert axis; tokens are dispatched to the devices
  owning their experts with `jax.lax.all_to_all` (the TPU equivalent of
  the ragged a2a), computed, and returned. Deliberately explicit because
  GSPMD cannot infer the token→expert shuffle without materializing the
  full dispatch tensor on every device.

Routing is standard top-k softmax gating with per-expert capacity
(drop-overflow) and the Switch-style load-balancing auxiliary loss.
Everything is static-shaped: capacity is a compile-time constant, drops
are masked writes — no dynamic shapes under jit (XLA requirement).

- `routed_experts` — one chip's share of an expert layer that drops
  nothing: the layer is told which experts it holds (`held`), routes
  over all of them (sigmoid scores, top-k, renormalised, scaled), sorts
  the (token, expert) pairs whose expert it holds by expert and runs
  grouped products (`jax.lax.ragged_dot`) over the held experts only.
  No capacity, no padding to one, no exchange: what the absent experts
  would add is left out, and nothing stands in for the chips that hold
  them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from kubeflow_tpu.parallel import mesh as mesh_lib


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    embed_dim: int = 512
    mlp_dim: int = 1024          # per-expert hidden dim (SwiGLU)
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32

    def capacity(self, n_tokens: int) -> int:
        """Per-expert token slots; static given a static token count."""
        cap = int(self.capacity_factor * n_tokens * self.top_k
                  / self.num_experts)
        return max(cap, self.top_k)


def init_moe(rng: jax.Array, cfg: MoEConfig) -> dict[str, jnp.ndarray]:
    kr, kg, ku, kd = jax.random.split(rng, 4)
    d, m, e = cfg.embed_dim, cfg.mlp_dim, cfg.num_experts
    s = d ** -0.5
    return {
        "router": (jax.random.normal(kr, (d, e)) * s).astype(cfg.dtype),
        "w_gate": (jax.random.normal(kg, (e, d, m)) * s).astype(cfg.dtype),
        "w_up": (jax.random.normal(ku, (e, d, m)) * s).astype(cfg.dtype),
        "w_down": (jax.random.normal(kd, (e, m, d)) * (m ** -0.5)).astype(cfg.dtype),
    }


def moe_logical_axes() -> dict[str, tuple[str | None, ...]]:
    """Logical axes for sharding.py rules ("experts" → tensor by default)."""
    return {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", None),
        "w_up": ("experts", "embed", None),
        "w_down": ("experts", None, "embed"),
    }


def _route(router_logits: jnp.ndarray, cfg: MoEConfig, capacity: int):
    """Top-k routing with capacity. logits: [T, E] (fp32 recommended).

    Returns:
      dispatch: [T, E, C] one-hot bool — token t occupies slot c of expert e
      combine:  [T, E, C] float — dispatch weighted by router probability
      frac:     [E] fraction of routing choices per expert
      mean_prob:[E] mean router probability per expert
    (aux loss = E * sum(frac * mean_prob), Switch Transformer eq. 4-6 —
    returned as factors so sharded callers can average them over token
    shards BEFORE the product, keeping the loss identical to the
    single-device computation.)
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)   # [T, k]

    # Slot assignment: for the flattened (k, T) priority order, each
    # expert's tokens take consecutive slots. Rank-0 choices across all
    # tokens outrank rank-1 choices (Switch convention) so a token's
    # primary expert is dropped last.
    expert_onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # [T,k,E]
    prio = expert_onehot.transpose(1, 0, 2).reshape(cfg.top_k * T, E)
    pos_in_expert = jnp.cumsum(prio, axis=0) - prio               # [kT, E]
    pos = pos_in_expert.reshape(cfg.top_k, T, E).transpose(1, 0, 2)
    slot = jnp.sum(pos * expert_onehot, axis=-1)                  # [T, k]
    keep = slot < capacity

    combine = jnp.zeros((T, E, capacity), jnp.float32)
    disp = jnp.zeros((T, E, capacity), bool)
    t_idx = jnp.arange(T)[:, None].repeat(cfg.top_k, 1)
    safe_slot = jnp.where(keep, slot, 0)
    combine = combine.at[
        t_idx.ravel(), gate_idx.ravel(), safe_slot.ravel()
    ].add(jnp.where(keep, gate_vals, 0.0).ravel())
    disp = disp.at[
        t_idx.ravel(), gate_idx.ravel(), safe_slot.ravel()
    ].max(keep.ravel())

    frac = jnp.mean(
        jnp.sum(expert_onehot, axis=1).astype(jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return disp, combine, frac, mean_prob


def _aux_loss(frac: jnp.ndarray, mean_prob: jnp.ndarray) -> jnp.ndarray:
    return frac.shape[0] * jnp.sum(frac * mean_prob)


def _expert_ffn(params, x_ecd: jnp.ndarray) -> jnp.ndarray:
    """Per-expert SwiGLU. x: [E, C, d] → [E, C, d]; E is a batched einsum
    dim so every expert's matmuls hit the MXU in one fused call."""
    gate = jnp.einsum("ecd,edm->ecm", x_ecd, params["w_gate"])
    up = jnp.einsum("ecd,edm->ecm", x_ecd, params["w_up"])
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    return jnp.einsum("ecm,emd->ecd", act, params["w_down"])


def moe_mlp(
    params: dict[str, jnp.ndarray],
    x: jnp.ndarray,            # [b, s, d]
    cfg: MoEConfig,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """GSPMD MoE layer: (output [b,s,d], aux loss). Shard params' experts
    dim via moe_logical_axes(); XLA inserts the collectives."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    capacity = cfg.capacity(b * s)
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    disp, combine, frac, mean_prob = _route(logits, cfg, capacity)
    # [T,E,C] x [T,d] → [E,C,d]: the dispatch einsum
    xe = jnp.einsum("tec,td->ecd", disp.astype(x.dtype), xt)
    ye = _expert_ffn(params, xe)
    y = jnp.einsum("tec,ecd->td", combine.astype(ye.dtype), ye)
    return y.reshape(b, s, d).astype(x.dtype), _aux_loss(frac, mean_prob)


def moe_mlp_expert_parallel(
    params: dict[str, jnp.ndarray],   # experts dim LOCAL (E/N per device)
    x: jnp.ndarray,                   # [b_local, s, d] tokens LOCAL
    cfg: MoEConfig,
    *,
    axis_name: str,
    token_axes: tuple[str, ...] = (),
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit expert parallelism. Call inside shard_map.

    `token_axes`: every mesh axis the token batch is sharded over
    (including `axis_name` when experts and tokens co-shard). The
    load-balance statistics are averaged over these axes *before* the
    frac·prob product, so the aux loss and its router gradient are
    bit-comparable to the unsharded `moe_mlp`.

    Capacity semantics (intended, GShard/Switch-style): capacity is
    derived from the LOCAL token count — each device grants every expert
    `capacity_factor * T_local * k / E` slots for its own tokens. Under
    tight capacity this drops per token-shard, not per global batch, so
    the same global batch can route differently on different mesh shapes
    and differs from `moe_mlp`'s global ranking. This is deliberate:
    exact global-drop parity would need a cross-device token ranking
    (a sort collective) before dispatch, defeating the point of EP. The
    per-shard semantics make each device's math identical to `moe_mlp`
    run on its local token block — tested that way in
    tests/test_moe.py::test_ep_tight_capacity_matches_per_shard_dense.

    Each device routes its local tokens against ALL experts (router
    weights replicated), builds capacity-bounded dispatch buffers, then a
    single `all_to_all` moves each expert-group's slots to the device
    owning those experts — the ragged all-to-all of SURVEY §2b, made
    rectangular by the capacity bound so shapes stay static. A second
    all_to_all returns expert outputs to the tokens' home devices.
    """
    n = jax.lax.psum(1, axis_name)
    b, s, d = x.shape
    T = b * s
    e_local = params["w_gate"].shape[0]
    E = e_local * n
    xt = x.reshape(T, d)
    capacity = cfg.capacity(T)

    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    disp, combine, frac, mean_prob = _route(logits, cfg, capacity)

    # Local dispatch buffers for every (global) expert: [E, C, d].
    xe = jnp.einsum("tec,td->ecd", disp.astype(x.dtype), xt)
    # a2a #1: split expert dim into N groups, concat along slots →
    # [E/N, N*C, d]: this device now holds ITS experts' slots from all
    # devices.
    xe = jax.lax.all_to_all(
        xe, axis_name, split_axis=0, concat_axis=1, tiled=True
    )
    ye = _expert_ffn(params, xe)
    # a2a #2 (inverse): [E/N, N*C, d] → [E, C, d] back on token owners.
    ye = jax.lax.all_to_all(
        ye, axis_name, split_axis=1, concat_axis=0, tiled=True
    )
    y = jnp.einsum("tec,ecd->td", combine.astype(ye.dtype), ye)
    # Average the statistics over every token-sharding axis FIRST, then
    # take the product — identical to the global single-device loss.
    for ax in (token_axes or (axis_name,)):
        frac = jax.lax.pmean(frac, ax)
        mean_prob = jax.lax.pmean(mean_prob, ax)
    return y.reshape(b, s, d).astype(x.dtype), _aux_loss(frac, mean_prob)


def moe_mlp_sharded(
    params: dict[str, jnp.ndarray],
    x: jnp.ndarray,               # [b, s, d] global
    cfg: MoEConfig,
    mesh: Mesh,
    *,
    expert_axis: str = mesh_lib.TENSOR_AXIS,
    batch_axes: tuple[str, ...] = (
        mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS, mesh_lib.TENSOR_AXIS,
    ),
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map wrapper: batch sharded over `batch_axes`, experts over
    `expert_axis` (EP reuses the tensor device axis per mesh.py).

    The expert axis is deliberately also a batch axis (the classic EP
    layout): tokens and experts shard along the same devices, so the
    all-to-alls move only the dispatched slots — no token replication.
    """
    n = mesh.shape[expert_axis]
    if cfg.num_experts % n:
        raise ValueError(
            f"num_experts={cfg.num_experts} not divisible by "
            f"{expert_axis}={n}"
        )
    n_batch = math.prod(mesh.shape[a] for a in batch_axes)
    if x.shape[0] % max(1, n_batch):
        raise ValueError(f"batch {x.shape[0]} not divisible by {batch_axes}")
    param_specs = {
        "router": P(),
        "w_gate": P(expert_axis),
        "w_up": P(expert_axis),
        "w_down": P(expert_axis),
    }
    x_spec = P(batch_axes, None, None)
    fn = jax.shard_map(
        functools.partial(
            moe_mlp_expert_parallel, cfg=cfg, axis_name=expert_axis,
            token_axes=tuple(batch_axes),
        ),
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(params, x)


# -- the share of a drop-free expert layer that this chip holds ------------


@dataclasses.dataclass(frozen=True)
class RoutedConfig:
    num_experts: int           # the router's outputs, held here or not
    top_k: int
    scale: float = 1.0         # on the renormalised weights


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_rows(x, idx, back, width):
    """x[idx], whose gradient is a gather too: `back` [rows of x,
    width] lists where each row of x went (every row goes to exactly
    `width` places), so the cotangent is `ct[back].sum(1)` and no
    scatter-add with repeated indices is ever run."""
    return x[idx]


def _gather_rows_fwd(x, idx, back, width):
    return x[idx], back


def _gather_rows_bwd(width, back, ct):
    dx = ct[back.reshape(-1)].reshape(back.shape[0], width, ct.shape[-1])
    return dx.sum(axis=1).astype(ct.dtype), None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def routed_experts(params, h: jnp.ndarray, cfg: RoutedConfig,
                   held: tuple[int, int]):
    """What the experts `held = (first, count)` add to the tokens
    h [T, d]. `params`: `router` [d, cfg.num_experts], `w_gate`, `w_up`
    [count, d, m], `w_down` [count, m, d]. -> (y [T, d], load [count]
    int32: the (token, expert) pairs each held expert took this call).

    Every token scores all `cfg.num_experts` experts and picks its
    `top_k`; a pair whose expert is not held adds nothing here. The
    held pairs are sorted by expert and each expert multiplies its own
    rows (SwiGLU). Shapes are static at `T * top_k` rows, the most that
    could be held; the grouped products do work for the held rows
    only. Scopes: `moe_route`, `moe_experts`."""
    first, count = held
    n_tok, k = h.shape[0], cfg.top_k
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,de->te", h, params["router"].astype(h.dtype),
            preferred_element_type=jnp.float32))
        top, expert = jax.lax.top_k(scores, k)                  # [T, k]
        weight = cfg.scale * top / jnp.sum(top, axis=-1, keepdims=True)
        local = expert.reshape(-1) - first
        is_held = (local >= 0) & (local < count)
        # held pairs first, by expert; the rest behind them
        key = jnp.where(is_held, local, count)
        order = jnp.argsort(key, stable=True)                   # [T * k]
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype),
            unique_indices=True)
        load = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                       dtype=jnp.int32)
        weight = jnp.where(is_held.reshape(n_tok, k), weight, 0.0)
    with jax.named_scope("moe_experts"):
        # rows past the held ones belong to no expert: the grouped
        # products neither read nor write them, so they are zeroed on
        # the way in and on the way out (and so are their cotangents)
        is_row = (jnp.arange(order.shape[0]) < jnp.sum(load))[:, None]
        rows = _gather_rows(h, order // k, back.reshape(n_tok, k), k)
        rows = jnp.where(is_row, rows, 0)
        gate = jax.lax.ragged_dot(rows, params["w_gate"].astype(h.dtype), load)
        up = jax.lax.ragged_dot(rows, params["w_up"].astype(h.dtype), load)
        out = jax.lax.ragged_dot(
            jax.nn.silu(gate) * up, params["w_down"].astype(h.dtype), load)
        out = jnp.where(is_row, out, 0)
        # back in (token, choice) order
        out = _gather_rows(out, back, order[:, None], 1)
        y = jnp.sum(out.reshape(n_tok, k, -1).astype(jnp.float32)
                    * weight[..., None], axis=1)
    return y.astype(h.dtype), load


# the gauges a step's expert loads feed (`load_stats`), with their help
LOAD_GAUGES = {
    "moe_held_assignments":
        "(token, expert) pairs the experts held here took in the last "
        "train step, over its expert layers",
    "moe_max_over_mean_load":
        "busiest held expert's load over the mean held load in the last "
        "train step, the largest over its expert layers",
}


def load_stats(load) -> dict[str, float]:
    """The two numbers a step's expert loads [layers, held] reduce to:
    the (token, expert) pairs the held experts took, and the largest
    over the layers of the busiest held expert's load over the mean."""
    load = np.asarray(load, dtype=np.float64).reshape(-1, np.shape(load)[-1])
    mean = np.maximum(load.mean(axis=1), 1e-9)
    return {"moe_held_assignments": float(load.sum()),
            "moe_max_over_mean_load": float((load.max(axis=1) / mean).max())}
