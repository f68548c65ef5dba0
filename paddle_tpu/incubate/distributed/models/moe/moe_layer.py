"""MoE layer with expert parallelism (reference:
python/paddle/incubate/distributed/models/moe/moe_layer.py:263 MoELayer —
gate → global_scatter/global_gather capacity-aware alltoall
(python/paddle/distributed/utils/moe_utils.py:20,:153) → experts).

TPU-native redesign (GSPMD MoE, the BASELINE.md config-5 mechanism):
capacity-based dispatch is expressed as static-shape einsums with one-hot
dispatch/combine tensors; expert parameters are stacked on a leading expert
dim and the expert apply is ``jax.vmap`` over that dim, laid out
``P('ep'/..., ...)`` — so the dispatch einsum makes the XLA partitioner emit
exactly the reference's global_scatter all-to-all and the combine einsum
emits global_gather.  No dynamic number_count/prune_gate_by_capacity
kernels: over-capacity tokens are dropped by buffer position at trace time
(GShard semantics).
"""

from __future__ import annotations

import math
from typing import List

import jax
import jax.numpy as jnp

from .....core.tensor import Tensor
from .....nn.layer import Layer, LayerList
from .....ops._prim import apply_op
from .....utils import extract_params, functional_call
from .gate import GShardGate, NaiveGate, SwitchGate


def _dispatch_combine(gate_val, gate_idx, num_experts, capacity):
    """One-hot dispatch [N,E,C] and weighted combine [N,E,C] tensors.

    Position within the expert buffer = rank of the token among those routed
    to that expert; tokens beyond capacity are dropped (GShard).
    """
    N, K = gate_idx.shape
    oh = jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.float32)  # [N,K,E]
    flat = oh.transpose(1, 0, 2).reshape(K * N, num_experts)       # k-major
    pos = jnp.cumsum(flat, axis=0) - flat                          # [K*N, E]
    pos = pos.reshape(K, N, num_experts).transpose(1, 0, 2)        # [N,K,E]
    pos = jnp.sum(pos * oh, axis=-1).astype(jnp.int32)             # [N,K]
    keep = pos < capacity
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)      # [N,K,C]
    disp = jnp.einsum("nke,nkc->nkec", oh, pos_oh) * keep[..., None, None]
    dispatch = jnp.clip(disp.sum(1), 0.0, 1.0)                     # [N,E,C]
    combine = jnp.einsum("nkec,nk->nec", disp, gate_val)           # [N,E,C]
    return dispatch, combine


def _dispatch_indices(gate_val, gate_idx, num_experts, capacity):
    """Index form of :func:`_dispatch_combine` — same slots, same k-major
    priority, same drops, but carried as int32 maps instead of [N, E, C]
    one-hots.  Delegates to the single-sourced
    ``kernels.grouped_matmul.capacity_dispatch_plan`` (the "gather"
    dispatch idiom of models.llama); returns (inv, slot, gate_keep)."""
    from .....kernels.grouped_matmul import capacity_dispatch_plan

    inv, slot, gate_keep, _ = capacity_dispatch_plan(
        gate_idx, gate_val, num_experts, capacity)
    return inv, slot, gate_keep


class MoELayer(Layer):
    """reference moe_layer.py:263.

    ``gate``: a config dict ({"type": "gshard"|"switch"|"naive",
    "top_k": k}) or a gate Layer.  ``experts``: LayerList of expert nets
    (identical structure enables the vmapped EP fast path; heterogeneous
    experts fall back to a python loop without EP).
    """

    def __init__(self, d_model: int, experts: List, gate=None, moe_group=None,
                 mp_group=None, recompute_interval: int = 0,
                 capacity_factor: float = 1.2, dispatch: str = "gather"):
        super().__init__()
        if dispatch not in ("gather", "einsum"):
            raise ValueError(
                f"dispatch must be 'gather' or 'einsum', got {dispatch!r}")
        # "gather" (default): int32 slot maps + row gathers — no [N, E, C]
        # one-hot dispatch tensor, no O(N*E*C*d) dispatch einsum (the
        # grouped-dispatch idiom of models.llama threaded through the
        # compat layer).  "einsum": the original GShard one-hot
        # contraction, kept as the reference oracle.
        self.dispatch = dispatch
        self.d_model = d_model
        if isinstance(experts, (list, tuple)):
            experts = LayerList(list(experts))
        self.experts = experts
        self.num_expert = len(experts)
        self.capacity_factor = capacity_factor
        self.group = moe_group

        if gate is None:
            gate = {"type": "gshard", "top_k": 2}
        if isinstance(gate, dict):
            cls = {"gshard": GShardGate, "switch": SwitchGate,
                   "naive": NaiveGate}[gate.get("type", "gshard")]
            gate = cls(d_model, self.num_expert, 1,
                       top_k=gate.get("top_k", 2 if cls is not SwitchGate else 1))
        self.gate = gate

        self._template = None
        pds = [extract_params(e) for e in self.experts]
        # homogeneity: identical param layout AND identical architecture
        # (repr covers class tree + extra_repr), else expert-0's math would
        # silently be applied with every expert's weights
        if (len({tuple(p.keys()) for p in pds}) == 1 and
                len({tuple(v.shape for v in p.values()) for p in pds}) == 1 and
                len({repr(e) for e in self.experts}) == 1):
            self._template = self.experts[0]

    @property
    def loss(self):
        return self.gate.loss

    def _capacity(self, num_tokens: int) -> int:
        cap = int(math.ceil(self.capacity_factor * num_tokens *
                            self.gate.top_k / self.num_expert))
        return max(cap, 4)

    def _ep_axis(self):
        from .....distributed.fleet.topology import get_hcg
        hcg = get_hcg()
        if hcg is None:
            return None
        mesh = hcg.global_mesh
        # EP rides its own axis when the mesh has one, else the sharding axis
        # (the reference maps EP groups over dp×sharding ranks)
        for ax in ("ep", "sharding", "dp"):
            if ax in mesh.axis_names and mesh.shape[ax] > 1 and \
                    self.num_expert % mesh.shape[ax] == 0:
                return mesh, ax
        return None

    def forward(self, x):
        from .....ops.manipulation import reshape, stack as pstack

        orig_shape = x.shape
        d = orig_shape[-1]
        xf = reshape(x, [-1, d])                                   # [N, d]
        N = xf.shape[0]
        cap = self._capacity(N)
        gate_val, gate_idx = self.gate(xf)
        E = self.num_expert
        ep = self._ep_axis()

        if self._template is None:
            return self._forward_python(xf, gate_val, gate_idx, cap, orig_shape)

        keys = list(extract_params(self._template).keys())
        # stacking through taped ops keeps grads flowing to each expert param
        stacked_tensors = [
            pstack([dict(e.named_parameters())[k] for e in self.experts], axis=0)
            for k in keys]
        template = self._template

        use_gather = self.dispatch == "gather"

        def prim(x_arr, val_arr, idx_arr, *leaves):
            from .....kernels.grouped_matmul import take_sentinel_rows

            d_ = x_arr.shape[-1]
            if use_gather:
                inv, slot, gate_keep = _dispatch_indices(
                    val_arr, idx_arr, E, cap)
                xin = take_sentinel_rows(x_arr, inv[:-1]) \
                    .reshape(E, cap, d_)
            else:
                dispatch, combine = _dispatch_combine(val_arr, idx_arr, E,
                                                      cap)
                xin = jnp.einsum("nec,nd->ecd",
                                 dispatch.astype(x_arr.dtype), x_arr)
            if ep is not None:
                mesh, ax = ep
                from jax.sharding import NamedSharding, PartitionSpec as P
                sh = lambda v: jax.lax.with_sharding_constraint(
                    v, NamedSharding(mesh, P(*([ax] + [None] * (v.ndim - 1)))))
                xin = sh(xin)
                leaves = tuple(sh(l) for l in leaves)
            stacked = dict(zip(keys, leaves))

            def one(params, ein):
                return functional_call(template, params, Tensor(ein))

            eout = jax.vmap(one)(stacked, xin)                     # [E, C, d]
            if use_gather:
                N_, K_ = idx_arr.shape
                eo = eout.reshape(E * cap, d_)
                picked = take_sentinel_rows(eo, slot)              # [K*N, d]
                y = (gate_keep[:, None].astype(eo.dtype) * picked) \
                    .reshape(K_, N_, d_).sum(axis=0)
                return y
            return jnp.einsum("nec,ecd->nd", combine.astype(eout.dtype), eout)

        y = apply_op("moe_gshard_einsum", prim,
                     tuple([xf, gate_val, gate_idx] + stacked_tensors))
        return reshape(y, list(orig_shape))

    def _forward_python(self, xf, gate_val, gate_idx, cap, orig_shape):
        from .....ops.manipulation import reshape, stack as pstack

        E = self.num_expert

        def prim_py(x_arr, val_arr, idx_arr):
            dispatch, combine = _dispatch_combine(val_arr, idx_arr, E, cap)
            xin = jnp.einsum("nec,nd->ecd", dispatch.astype(x_arr.dtype), x_arr)
            return xin, combine.astype(x_arr.dtype)

        xin, combine = apply_op("moe_dispatch", prim_py, (xf, gate_val, gate_idx))
        eout = pstack([e(xin[i]) for i, e in enumerate(self.experts)], axis=0)
        y = apply_op("moe_combine",
                     lambda c, eo: jnp.einsum("nec,ecd->nd", c, eo),
                     (combine, eout))
        return reshape(y, list(orig_shape))
