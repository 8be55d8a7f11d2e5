"""Sharding rules: PartitionSpecs for params, optimizer state, batches, and
caches on the production mesh (DESIGN.md §5).

Layout summary
  mesh axes     single-pod (data=16, model=16); multi-pod (pod=2, data=16, model=16)
  TP ("model")  attention q/k/v/o columns-rows, MLP hidden, MoE experts,
                vocab/embedding
  DP (pod,data) batch dimension (training + serving)
  FSDP ("data") second weight dim during TRAINING (ZeRO-3-style: weights,
                grads, and Adam moments all sharded over data; XLA inserts the
                per-layer all-gather / reduce-scatter inside the layer scan).
                Serving keeps weights TP-only unless the model cannot fit
                (dbrx-132b), where FSDP stays on.
  KV caches     batch over DP; kv-heads over "model" when divisible, else
                head_dim over "model" (the contraction all-reduces over
                model — MQA/GQA-friendly, see DESIGN.md).

Every rule degrades to None when a dim is not divisible by the axis size
(GSPMD would pad; we prefer explicit replication and let the roofline's
MODEL_FLOPS/HLO ratio expose any waste we keep).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

_REPLICATED_NAMES = {
    "norm", "norm1", "norm2", "final_norm", "A_log", "D", "dt_bias",
    "conv_b", "conv_w", "router", "len",
}

SERVE_FSDP_BYTES = 8 << 30      # params/chip above this forces FSDP at serve


def dp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def param_bytes(cfg: ModelConfig) -> int:
    bpp = 2 if cfg.param_dtype == "bfloat16" else 4
    return cfg.num_params * bpp


def needs_serve_fsdp(cfg: ModelConfig, model_shards: int = 16) -> bool:
    return param_bytes(cfg) / model_shards > SERVE_FSDP_BYTES


@dataclass
class ShardingRules:
    mesh: Mesh
    cfg: ModelConfig
    train: bool = True

    # -- helpers -----------------------------------------------------------------
    def _ax(self, axis, size):
        if axis is None:
            return None
        n = int(np.prod([self.mesh.shape[a] for a in
                         (axis if isinstance(axis, tuple) else (axis,))]))
        return axis if size % n == 0 else None

    @property
    def _fsdp(self):
        if self.train:
            return "data"
        return "data" if needs_serve_fsdp(self.cfg,
                                          self.mesh.shape["model"]) else None

    @property
    def _dp(self):
        return dp_axes(self.mesh)

    # -- params --------------------------------------------------------------------
    def _param_spec(self, path, shape) -> P:
        name = None
        for k in reversed(path):
            key = getattr(k, "key", None)
            if isinstance(key, str):
                name = key
                break
        nd = len(shape)
        lead = (None,) * (nd - 2)
        f, m = self._fsdp, "model"
        if name in _REPLICATED_NAMES or nd <= 1:
            return P()
        if name == "embed":
            return P(self._ax(m, shape[0]), self._ax(f, shape[1]))
        if name == "lm_head":
            return P(self._ax(f, shape[0]), self._ax(m, shape[1]))
        if name in ("wq", "wk", "wv", "w1", "w3", "in_proj"):
            if nd == 4:      # MoE expert stack (L, E, D, F): experts over
                # model, FSDP on F (column-split): contracting D stays
                # shard-local, so no giant partial-sum all-reduce (§Perf,
                # dbrx prefill: 28 GB/layer -> (B,E,cap,D) once)
                return P(None, self._ax(m, shape[1]), None,
                         self._ax(f, shape[3]))
            return P(*lead, self._ax(f, shape[-2]), self._ax(m, shape[-1]))
        if name in ("wo", "w2", "out_proj"):
            if nd == 4:      # MoE w2 (L, E, F, D): FSDP on F (row-split),
                # paired with w1/w3 so h flows shard-local through the MLP
                return P(None, self._ax(m, shape[1]),
                         self._ax(f, shape[2]), None)
            return P(*lead, self._ax(m, shape[-2]), self._ax(f, shape[-1]))
        if name in ("bq", "bk", "bv"):
            # stacked-per-layer biases are (L, dim): only the LAST dim is TP
            return P(*((None,) * (nd - 1)), self._ax(m, shape[-1]))
        return P()           # conservative default: replicate

    def param_specs(self, params_shapes):
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: self._param_spec(p, leaf.shape), params_shapes)

    def opt_specs(self, opt_shapes, params_shapes):
        """Adam m/v mirror the (train) param layout; step is replicated."""
        pspecs = self.param_specs(params_shapes)
        return {"m": pspecs, "v": pspecs, "step": P()}

    # -- batches ----------------------------------------------------------------------
    def _batched(self, shape) -> P:
        b = self._ax(self._dp, shape[0])
        return P(b, *(None,) * (len(shape) - 1))

    def batch_specs(self, batch_shapes):
        return jax.tree.map(lambda leaf: self._batched(leaf.shape),
                            batch_shapes)

    # -- caches -----------------------------------------------------------------------
    def _cache_spec(self, path, shape) -> P:
        name = None
        for k in reversed(path):
            key = getattr(k, "key", None)
            if isinstance(key, str):
                name = key
                break
        if name == "len":
            return P(self._ax(self._dp, shape[0]))
        b = self._ax(self._dp, shape[1])
        if name in ("k", "v"):
            # (L|G, B, KH, S, hd): batch over DP, SEQUENCE over model —
            # flash-decoding-style split: each model shard attends over its
            # S-chunk and GSPMD combines with small all-reduces (max/sum of
            # the online softmax + the (B,H,hd) output). Uniform across GQA/
            # MQA/MHA head counts, unlike head sharding (DESIGN.md §5).
            # kv-heads-major layout: seq is dim 3.
            return P(None, b, None, self._ax("model", shape[3]), None)
        if name == "ssm":      # (L, B, H, Phead, N)
            return P(None, b, self._ax("model", shape[2]), None, None)
        if name == "conv":     # (L, B, K-1, Ch)
            return P(None, b, None, self._ax("model", shape[3]))
        return P(*(None,) * len(shape))

    def cache_specs(self, cache_shapes):
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: self._cache_spec(p, leaf.shape), cache_shapes)

    # -- materialization -----------------------------------------------------------------
    def named(self, spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))


class ServeSharding:
    """Serving-time placement policy for tensor-parallel inference.

    One object per backend, built from the engine's mesh. The placement
    contract the sharded engine relies on:

    * **params** — TP-sharded by :class:`ShardingRules` (``train=False``):
      attention q/k/v and MLP columns over ``model``, wo/w2 rows over
      ``model``, MoE expert stacks over ``model`` (expert-parallel decode
      falls out of the einsum), everything small replicated.
    * **KV** — paged pools ``(L, NP, KH, page, hd)`` and slot caches
      ``(L, B, KH, S, hd)`` shard the kv-head axis over ``model``; when the
      head count is not divisible (GQA/MQA on a wide mesh) the head_dim
      axis shards instead, and when neither divides the cache replicates.
      Block tables / lengths / refcounts are host-side and replicated.
    * **everything the sampler touches** — decode state, tables, lens,
      token uploads — is replicated, so every shard samples the same token
      from its full (all-gathered) logits and only O(max_slots) ids ever
      sync to the host: the zero-logits-transfer invariant survives
      sharding.

    ``pin_*`` wrap ``with_sharding_constraint`` and are applied inside the
    jitted bodies so carried cache/state shardings are fixed points across
    calls (donation stays effective, GSPMD never drifts the layout).
    """

    def __init__(self, mesh: Mesh, cfg: ModelConfig):
        if "model" not in mesh.axis_names:
            raise ValueError(
                f"serving mesh needs a 'model' axis, got {mesh.axis_names}; "
                f"build one with launch.make_local_mesh(data, model)")
        self.mesh = mesh
        self.cfg = cfg
        self.rules = ShardingRules(mesh, cfg, train=False)
        self.replicated = NamedSharding(mesh, P())

    @property
    def model_shards(self) -> int:
        return int(self.mesh.shape["model"])

    # -- placement (device_put, at init / upload time) ---------------------------
    def param_shardings(self, params_shapes):
        """NamedSharding tree for params (arrays or ShapeDtypeStructs): the
        ``out_shardings`` that initialize them in place on the mesh."""
        return self.rules.named(self.rules.param_specs(params_shapes))

    def shard_params(self, params):
        return jax.device_put(params, self.param_shardings(params))

    def _head_axes(self, kh: int, hd: int):
        """(kv-head axis, head_dim axis): kv-heads over model when
        divisible, else head_dim over model, else replicate."""
        if self.rules._ax("model", kh) is not None:
            return "model", None
        if self.rules._ax("model", hd) is not None:
            return None, "model"
        return None, None

    def pool_spec(self, shape) -> P:
        """Paged KV pool (L, num_pages, KH, page_size, hd), head-major."""
        kh, hd = self._head_axes(shape[2], shape[4])
        return P(None, None, kh, None, hd)

    def view_spec(self, shape) -> P:
        """Gathered context view (L, B, S, KH, hd): same head-axis policy
        as the pool it was gathered from, batch/seq replicated (the fused
        twin's split attention contracts over S per shard)."""
        kh, hd = self._head_axes(shape[3], shape[4])
        return P(None, None, None, kh, hd)

    def slot_cache_spec(self, name: str, shape) -> P:
        """Slot cache leaf by name: k/v are (L, B, KH, S, hd); len and the
        SSM/conv states replicate."""
        if name in ("k", "v"):
            kh, hd = self._head_axes(shape[2], shape[4])
            return P(None, None, kh, None, hd)
        return P()

    def pool_zeros(self, shape, dtype):
        """A zero page pool allocated shard-by-shard on the mesh (never
        whole on one device first)."""
        return jnp.zeros(shape, dtype,
                         device=NamedSharding(self.mesh, self.pool_spec(shape)))

    def shard_slot_cache(self, cache):
        return {n: jax.device_put(
            a, NamedSharding(self.mesh, self.slot_cache_spec(n, a.shape)))
            for n, a in cache.items()}

    def replicate(self, x):
        """Host upload, replicated onto the mesh's device set (mixing a
        committed single-device array into a mesh jit is an error)."""
        return jax.device_put(x, self.replicated)

    # -- constraints (with_sharding_constraint, inside jit) ----------------------
    def pin(self, x, spec: P):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def pin_replicated(self, tree):
        return jax.tree.map(lambda a: self.pin(a, P()), tree)

    def pin_pools(self, pools):
        return {n: self.pin(a, self.pool_spec(a.shape))
                for n, a in pools.items()}

    def pin_view(self, view):
        return {n: self.pin(a, self.view_spec(a.shape))
                for n, a in view.items()}

    def pin_slot_cache(self, cache):
        return {n: self.pin(a, self.slot_cache_spec(n, a.shape))
                for n, a in cache.items()}
