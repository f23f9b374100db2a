"""Pipeline-parallel SigLIP tower forwards: the block stack as gpipe stages.

Round-2 left :mod:`parallel.pipeline` a library (oracle-tested on toy stacks);
this module makes it a *capability*: the real ViT / text towers run their
encoder blocks through the GPipe schedule over a ``pp`` mesh axis, composing
with data parallelism (batch stays ``dp``-sharded through GSPMD — gpipe's
``shard_map`` manualizes only ``pp``).

Design: a scanned tower already stores its blocks stage-ready — ``nn.scan``
stacks every block param with a leading ``depth`` axis
(models/transformer.py:326-332), and :func:`pipeline.stack_stage_params` just
reshapes ``(depth, ...) -> (S, depth/S, ...)``, so pipeline placement is a
sharding annotation, not a new param layout. The pre-block (patch/token embed)
and post-block (final LN, pooling, projection) pieces are tiny; they run
replicated-over-``pp`` via the same flax submodules the towers use, applied as
pure functions over the extracted param subtrees. Exactness vs the plain tower
forward is pinned in tests/test_pp_towers.py.

The reference has no model layer at all (its towers are toy Linears,
/root/reference/test_distributed_sigmoid_loss.py:71-76); pipeline parallelism
is part of the beyond-reference scale story alongside dp/tp/sp/ep.

Constraints (validated): towers must be ``scan_layers=True`` (stage-major
params), ``depth % pp == 0``, no sequence parallelism inside a pipelined tower
(nested manual ``shard_map`` axes), and no MoE (the router's sown aux losses
cannot ride ``Block.apply`` under the schedule).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from distributed_sigmoid_loss_tpu.models.transformer import (
    Block,
    MapHead,
    _dtype,
    _remat_policy,
)
from distributed_sigmoid_loss_tpu.models.vit import PatchEmbed
from distributed_sigmoid_loss_tpu.ops.sigmoid_loss import l2_normalize
from distributed_sigmoid_loss_tpu.parallel.microbatch import (
    microbatch_merge,
    microbatch_split,
)
from distributed_sigmoid_loss_tpu.parallel.pipeline import (
    gpipe,
    make_layer_stage_fn,
    pipeline_axis,
    stack_stage_params,
)
from distributed_sigmoid_loss_tpu.utils.config import (
    SigLIPConfig,
    TextConfig,
    ViTConfig,
    changed_block_options,
    tower_quant_mode,
)

__all__ = [
    "siglip_forward_pp",
    "text_forward_pp",
    "validate_pp_tower",
    "vision_forward_pp",
]


def validate_pp_tower(cfg: ViTConfig | TextConfig, num_stages: int, name: str) -> None:
    """Raise with an actionable message when a tower can't be pipelined."""
    changed = changed_block_options(cfg)
    if changed:
        # This module re-implements the tower around its blocks (position
        # table, LayerNorm final norm, one pass): it must not run another
        # block as the SigLIP one.
        raise ValueError(
            f"{name}: pipelined towers run the SigLIP block only (LayerNorm, "
            "biased GELU MLP, learned positions, one pass); not built for "
            + ", ".join(changed)
        )
    if not cfg.scan_layers:
        raise ValueError(
            f"{name}: pipeline parallelism needs scan_layers=True (stage params "
            "are the nn.scan-stacked block leaves)"
        )
    if cfg.depth % num_stages:
        raise ValueError(
            f"{name}: depth {cfg.depth} must divide into {num_stages} pipeline "
            "stages"
        )
    if cfg.sequence_parallel_axis is not None:
        raise ValueError(
            f"{name}: sequence parallelism inside a pipelined tower would nest "
            "manual shard_maps; run sp XOR pp per tower"
        )
    if cfg.moe_experts:
        raise ValueError(
            f"{name}: MoE blocks sow router aux losses, which Block.apply under "
            "the pipeline schedule would silently drop; pp towers must be dense"
        )


def _pipelined_blocks(
    cfg: ViTConfig | TextConfig,
    block_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    causal: bool = False,
    axis_name: str = pipeline_axis,
    enclosing_manual: bool = False,
) -> jax.Array:
    """Run the (depth,)-stacked block params over ``x`` via the gpipe schedule.

    ``enclosing_manual``: caller is already inside a shard_map manual over
    ``axis_name`` (and possibly data axes — the compressed step's
    ``(dcn, dp, pp)`` region). ``block_params`` leaves are then the LOCAL
    stage slice ``(depth/S, ...)`` and ``x`` the local batch rows; the
    microbatch split is a plain contiguous reshape (rows are already
    device-local, so the GSPMD-interleaved split is unnecessary) and gpipe
    runs its device-level schedule directly.
    """
    num_stages = mesh.shape[axis_name]
    dtype = _dtype(cfg.dtype)
    block = Block(
        cfg.width, cfg.num_heads, cfg.mlp_ratio, dtype,
        attn_impl=cfg.attn_impl, causal=causal,
        # Same dot injection as the scanned tower (incl. the trainable STE
        # mode) — without this a quantized config would silently run its
        # pipelined blocks full-precision, and the exactness oracle vs the
        # plain tower forward would mask nothing else.
        quant=tower_quant_mode(cfg),
    )

    def layer_apply(p, xx):
        return block.apply({"params": p}, xx)

    if cfg.remat:
        # Per-layer remat with the tower's policy — same granularity the
        # non-pp scan path uses, so the HBM/recompute trade carries over.
        layer_apply = jax.checkpoint(
            layer_apply, policy=_remat_policy(cfg.remat_policy),
            prevent_cse=False,
        )
    stage_fn = make_layer_stage_fn(layer_apply)
    if enclosing_manual:
        # Local stage slice arrives pre-sliced by the enclosing shard_map's
        # P(pp) in_spec; sanity-check it is one stage's worth of layers.
        local_depth = jax.tree.leaves(block_params)[0].shape[0]
        if local_depth * num_stages != cfg.depth:
            raise ValueError(
                f"enclosing_manual expects per-stage block params "
                f"(depth/S = {cfg.depth // num_stages} layers), got leading "
                f"dim {local_depth}"
            )
        if x.shape[0] % num_microbatches:
            raise ValueError(
                f"local batch {x.shape[0]} must divide into "
                f"{num_microbatches} pp microbatches"
            )
        xs = x.reshape((num_microbatches, -1) + x.shape[1:])
        ys = gpipe(
            stage_fn, block_params, xs, mesh=mesh, axis_name=axis_name,
            stream_io=False, enclosing_manual=True,
        )
        return ys.reshape((-1,) + x.shape[1:])
    stage_params = stack_stage_params(block_params, num_stages)
    # Row order is preserved: split -> pipeline -> exact-inverse merge, so the
    # loss's positive-pair diagonal survives the microbatching.
    xs = microbatch_split(x, num_microbatches, mesh, what="pp_microbatches")
    # stream_io whenever the schedule allows (S | M — true for the default
    # M = 2S): the (M, ...) in/out buffers shard over pp instead of
    # replicating, cutting per-stage activation-buffer HBM S-fold.
    ys = gpipe(
        stage_fn, stage_params, xs, mesh=mesh, axis_name=axis_name,
        stream_io=num_microbatches % num_stages == 0,
    )
    return microbatch_merge(ys, mesh)


def vision_forward_pp(
    cfg: ViTConfig,
    params,
    images: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = pipeline_axis,
    enclosing_manual: bool = False,
) -> jax.Array:
    """ViT forward ≡ ``models.vit.ViT.__call__`` with pipelined blocks.

    ``params`` is the tower's (unboxed) param subtree; the pre/post pieces
    reuse the exact flax submodules of the tower, so any future change to the
    tower that this function misses trips the exactness oracle.
    """
    validate_pp_tower(cfg, mesh.shape[axis_name], "vision")
    dtype = _dtype(cfg.dtype)
    x = images.astype(dtype)
    x = PatchEmbed(cfg.width, cfg.patch_size, dtype).apply(
        {"params": params["patch_embed"]}, x
    )
    x = x + params["pos_embed"].astype(dtype)

    x = _pipelined_blocks(
        cfg, params["encoder"]["blocks"]["block"], x,
        mesh=mesh, num_microbatches=num_microbatches, axis_name=axis_name,
        enclosing_manual=enclosing_manual,
    )
    x = nn.LayerNorm(dtype=dtype).apply(
        {"params": params["encoder"]["ln_final"]}, x
    )
    if cfg.pool == "map":
        x = MapHead(cfg.width, cfg.num_heads, cfg.mlp_ratio, dtype).apply(
            {"params": params["map_head"]}, x
        )
    else:
        x = x.mean(axis=1)
    if cfg.use_proj:
        x = nn.Dense(cfg.embed_dim, dtype=dtype).apply(
            {"params": params["proj"]}, x
        )
    return x.astype(jnp.float32)


def text_forward_pp(
    cfg: TextConfig,
    params,
    token_ids: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = pipeline_axis,
    enclosing_manual: bool = False,
) -> jax.Array:
    """Text forward ≡ ``models.text.TextTransformer.__call__`` with pipelined
    blocks."""
    validate_pp_tower(cfg, mesh.shape[axis_name], "text")
    dtype = _dtype(cfg.dtype)
    emb = nn.Embed(cfg.vocab_size, cfg.width).apply(
        {"params": params["token_embed"]}, token_ids
    )
    x = emb.astype(dtype) + params["pos_embed"].astype(dtype)

    x = _pipelined_blocks(
        cfg, params["encoder"]["blocks"]["block"], x,
        mesh=mesh, num_microbatches=num_microbatches, causal=cfg.causal,
        axis_name=axis_name, enclosing_manual=enclosing_manual,
    )
    x = nn.LayerNorm(dtype=dtype).apply(
        {"params": params["encoder"]["ln_final"]}, x
    )
    if cfg.pool == "map":
        x = MapHead(cfg.width, cfg.num_heads, cfg.mlp_ratio, dtype).apply(
            {"params": params["map_head"]}, x
        )
    else:
        x = x[:, -1]
    x = nn.Dense(cfg.embed_dim, dtype=dtype).apply({"params": params["proj"]}, x)
    return x.astype(jnp.float32)


def siglip_forward_pp(
    cfg: SigLIPConfig,
    params,
    images: jax.Array,
    token_ids: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = pipeline_axis,
    enclosing_manual: bool = False,
):
    """Drop-in for ``SigLIP.apply``: ``(zimg, ztxt, loss_params)`` with both
    towers' blocks pipelined over ``axis_name``. ``enclosing_manual``: see
    :func:`_pipelined_blocks` — the compressed step's fully-manual region."""
    zimg = l2_normalize(
        vision_forward_pp(
            cfg.vision, params["visual"], images,
            mesh=mesh, num_microbatches=num_microbatches, axis_name=axis_name,
            enclosing_manual=enclosing_manual,
        )
    )
    ztxt = l2_normalize(
        text_forward_pp(
            cfg.text, params["textual"], token_ids,
            mesh=mesh, num_microbatches=num_microbatches, axis_name=axis_name,
            enclosing_manual=enclosing_manual,
        )
    )
    return zimg, ztxt, {"t_prime": params["t_prime"], "bias": params["bias"]}
