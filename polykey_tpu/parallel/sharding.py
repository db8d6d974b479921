"""Partition specs: how parameters, caches, and activations shard on the mesh.

Megatron-style tensor parallelism for the transformer block: column-parallel
first matmuls (wq/wk/wv, gate/up shard their *output* features over ``tp``),
row-parallel second matmuls (wo, down shard their *input* features), so the
only cross-device traffic per block is the reduce of the row-parallel output
— which XLA's SPMD partitioner emits as reduce-scatter/all-gather pairs over
the ICI ``tp`` axis on its own; no hand-written collectives.

Other axes: the stacked layer dim shards over ``pp``; MoE expert dims over
``ep``; the KV page pool shards its head dim over ``tp``; the decode batch
shards over ``dp``.

GQA constraint: num_kv_heads must divide by tp (Llama-3-8B: 8 kv heads →
tp ∈ {1,2,4,8}).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig

# Leaf-path (within a layer) → PartitionSpec *without* the leading stacked
# layer axis (added uniformly below as the pp dimension).
_LAYER_RULES: dict[tuple[str, ...], P] = {
    ("attn", "wq"): P(None, "tp"),
    ("attn", "wk"): P(None, "tp"),
    ("attn", "wv"): P(None, "tp"),
    ("attn", "wo"): P("tp", None),
    ("mlp", "gate"): P(None, "tp"),
    ("mlp", "up"): P(None, "tp"),
    ("mlp", "down"): P("tp", None),
    ("router",): P(None, None),
    ("experts", "gate"): P("ep", None, "tp"),
    ("experts", "up"): P("ep", None, "tp"),
    ("experts", "down"): P("ep", "tp", None),
    ("ln1",): P(None),
    ("ln2",): P(None),
    ("post_ln1",): P(None),
    ("post_ln2",): P(None),
}

_TOP_RULES: dict[tuple[str, ...], P] = {
    ("embed",): P("tp", None),     # vocab-sharded; lookup gathers over tp
    ("final_norm",): P(None),
    ("lm_head",): P(None, "tp"),   # logits shard over vocab on tp
    ("exit_gate", "w"): P(None, None),   # a looped stack's exit gate
    ("exit_gate", "b"): P(None),
}


def _spec_for_path(
    path: tuple[str, ...], leaf=None, mesh: Optional[Mesh] = None
) -> P:
    # Quantized leaves (models/quant.py QuantizedTensor): `q` keeps the
    # weight's spec. int8 `s` is the weight shape minus the contraction
    # (-2) axis, so its spec is the weight spec with that axis dropped
    # (e.g. wq [L, H, out] P("pp", None, "tp") → s [L, out] P("pp", "tp")).
    # int4 `s` is group-wise [..., in/g, out] — SAME rank as q with the
    # group axis in the contraction position, so a tp-sharded contraction
    # axis shards the groups the same way WHEN the group count divides;
    # otherwise (tiny models: one group) the group axis replicates and
    # GSPMD re-shards at the dequant reshape. Discriminated by rank.
    if path and path[-1] in ("q", "s"):
        base = _spec_for_path(path[:-1])
        if path[-1] == "q":
            return base
        ndim = getattr(leaf, "ndim", -1)
        if ndim == len(base):                # group-wise (int4)
            contr = base[-2]
            if contr is not None and mesh is not None:
                axes = contr if isinstance(contr, tuple) else (contr,)
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                if leaf.shape[-2] % size != 0:
                    return P(*base[:-2], None, base[-1])
            return base
        return P(*base[:-2], base[-1]) if len(base) >= 2 else base
    if path in _TOP_RULES:
        return _TOP_RULES[path]
    if path and path[0] == "layers":
        layer_path = path[1:]
        if layer_path in _LAYER_RULES:
            inner = _LAYER_RULES[layer_path]
            return P("pp", *inner)  # leading stacked-layer axis → pp
    raise KeyError(f"no sharding rule for param path {path}")


def _path_keys(path) -> tuple[str, ...]:
    keys = []
    for entry in path:
        if isinstance(entry, jax.tree_util.DictKey):
            keys.append(str(entry.key))
        elif isinstance(entry, jax.tree_util.GetAttrKey):
            keys.append(entry.name)  # QuantizedTensor fields: 'q' / 's'
        else:
            keys.append(str(entry))
    return tuple(keys)


def param_shardings(cfg: ModelConfig, mesh: Mesh, params_tree=None):
    """NamedSharding pytree matching init_params' structure."""
    if params_tree is None:
        from ..models.transformer import init_params

        params_tree = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg)
        )
    if cfg.layer_pattern:
        # A layer pattern runs on one device (engine/config.py refuses a
        # mesh for it): its kind-grouped tree is placed whole.
        return jax.tree.map(lambda _: NamedSharding(mesh, P()), params_tree)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, _spec_for_path(_path_keys(path), leaf, mesh)
        ),
        params_tree,
    )


def shard_params(params: dict, cfg: ModelConfig, mesh: Mesh) -> dict:
    """Place a param pytree onto the mesh under the TP/PP/EP specs."""
    return jax.device_put(params, param_shardings(cfg, mesh, params))


def init_sharded_params(
    key: jax.Array, cfg: ModelConfig, mesh: Mesh, dtype,
    quantize_bits: Optional[int] = None,
) -> dict:
    """Random-init params born in their final dtype and sharding — the
    serving engine's start when no checkpoint is given.

    `shard_params(quantize_params(init_params(key, cfg, dtype)))` lands
    the whole `dtype` tree on one device first: 16 GB of bf16 for an 8B
    model on a 16 GB chip, whatever the mesh or the quantization. Here
    each layer is drawn (and quantized) alone on the mesh's first device
    and written into the preallocated, already-sharded layer stack by a
    donated in-place update, so a device holds its shard of the final
    tree plus one layer of temporaries.

    The values are init_params' bit for bit: a layer's draws depend only
    on its key, quantization reduces within a layer, and generation stays
    op-by-op like init_params itself — inside one jit XLA folds
    `(sqrt2 * erfinv(u)) * scale` and divisions by constants into
    differently-rounded forms.
    """
    from ..models.quant import quantize_embed, quantize_linears
    from ..models.transformer import init_layer_params, init_top_params

    if cfg.layer_pattern:
        from ..models.hybrid import init_params as init_hybrid_params

        if quantize_bits:
            raise ValueError("a layer pattern has no quantized weights yet")
        with jax.default_device(mesh.devices.flat[0]):
            return shard_params(init_hybrid_params(key, cfg, dtype), cfg, mesh)

    def one_layer(k):
        layer = init_layer_params(k, cfg, dtype)
        return quantize_linears(layer, quantize_bits) if quantize_bits else layer

    def top_params(k_embed, k_head):
        top = init_top_params(k_embed, k_head, cfg, dtype)
        if quantize_bits:
            embed = quantize_embed(top["embed"])
            top = {**quantize_linears(top, quantize_bits), "embed": embed}
        return top

    L = cfg.num_layers
    with jax.default_device(mesh.devices.flat[0]):
        k_embed, k_layers, k_head = jax.random.split(key, 3)
        layer_keys = jax.random.split(k_layers, L)
        stack_shape = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((L, *x.shape), x.dtype),
            jax.eval_shape(one_layer, layer_keys[0]),
        )
        shardings = param_shardings(cfg, mesh, {
            **jax.eval_shape(top_params, k_embed, k_head),
            "layers": stack_shape,
        })
        stack_sh = shardings.pop("layers")
        # Top first: the embedding's f32 quantization temporaries are the
        # largest of the whole init, and nothing else is resident yet.
        top = jax.device_put(top_params(k_embed, k_head), shardings)
        stack = jax.tree.map(
            lambda x, sh: jnp.zeros(x.shape, x.dtype, device=sh),
            stack_shape, stack_sh,
        )
        leaves, treedef = jax.tree.flatten(stack_sh)
        put_layer = _put_layer_jit(treedef, tuple(leaves))
        for idx in range(L):
            stack = put_layer(
                stack, one_layer(layer_keys[idx]), jnp.int32(idx)
            )
    return {**top, "layers": stack}


@functools.lru_cache(maxsize=8)
def _put_layer_jit(treedef, shardings: tuple):
    """The donated in-place write of one layer into the stacked tree,
    pinned to the stack's shardings. Cached per sharding tree so every
    engine built on the same mesh reuses one traced, compiled function
    (a fresh jit per engine cost a retrace + compile each time)."""
    def put_layer(stack, layer, idx):
        return jax.tree.map(
            lambda s, x: jax.lax.dynamic_update_index_in_dim(s, x, idx, 0),
            stack, layer,
        )

    return jax.jit(
        put_layer, donate_argnums=0,
        out_shardings=jax.tree.unflatten(treedef, shardings),
    )


def paged_kv_sharding(mesh: Mesh) -> NamedSharding:
    """The page pool [L, N, 2, page_size, Hk·D] (stored layout:
    engine/kv_cache.py): the last dimension shards over tp — heads are
    major in the fold, so a shard is Hk/tp whole heads, K and V of a page
    alike.

    Pages are *not* dp-sharded: any decode slot may hold any page, so the
    pool replicates over dp (each dp replica serves its own slot subset with
    its own pool in the dp>1 serving layout).
    """
    return NamedSharding(mesh, P("pp", None, None, None, "tp"))


def kv_scale_sharding(mesh: Mesh) -> NamedSharding:
    """The int8-KV scale pools [L, N, page_size, Hk]: heads over tp, as
    the pool they scale."""
    return NamedSharding(mesh, P("pp", None, None, "tp"))


def contiguous_kv_sharding(mesh: Mesh) -> NamedSharding:
    """Contiguous cache [L, B, S, Hk, D]: batch over dp, heads over tp."""
    return NamedSharding(mesh, P("pp", "dp", None, "tp", None))


def batch_sharding(mesh: Mesh, ndim: int, seq_axis: Optional[int] = None):
    """Token batches [B, T, ...]: batch over dp, optionally T over sp."""
    spec = ["dp"] + [None] * (ndim - 1)
    if seq_axis is not None:
        spec[seq_axis] = "sp"
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
