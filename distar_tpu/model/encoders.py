"""Observation encoders: scalar, spatial, entity, and value-feature.

Role parity with the reference encoders
(reference: distar/agent/default/model/obs_encoder/*.py, encoder.py) with
TPU-first reformulations:

* Entity features are projected as the reference projects them
  (entity_encoder.py:59-80): each entity's 997-wide row of one-hots, position
  bits and raw floats times one ``[997, width]`` matrix, one product forward
  and one (``rows^T x g``) for every table's gradient. The parameters stay one
  leaf a field (the matrix is their concatenation, made at trace time), and the
  rows are rebuilt in the backward pass, not kept. A table a field with a
  gather each and their sum, the form this module had until PR 26, was the
  flagship step's largest avoidable cost on the v5e: 26 gathers forward and 26
  scatter-adds backward that write one row at a time (2.0 ms each at 196,608
  rows, 52.7 ms a step), where the whole product is 100 GFLOP (0.5 ms).
* Spatial maps are NHWC (TPU conv layout); effect coordinate lists are
  scattered into planes with one fused scatter.
* All fixed shapes: entities padded to MAX_ENTITY_NUM, map fixed 152x160.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from .config import cdtype, static_cfg
from ..ops import (
    Conv2DBlock,
    FCBlock,
    ResBlock,
    Transformer,
    AttentionPool,
    binary_encode,
    one_hot,
    scatter_connection,
    sequence_mask,
)
from ..ops.transformer import TransformerLayer
from ..ops.blocks import build_activation


class _FieldTable(nn.Module):
    """One field's rows of the entity projection: the leaf ``<name>/embedding``
    that ``nn.Embed`` would hold, or ``<name>/kernel`` as ``nn.Dense`` would,
    drawn by that module's initialiser (checkpoints and ``ref_convert`` see
    the same tree and the same values from the same seed)."""

    rows: int
    width: int
    arc: str

    @nn.compact
    def __call__(self):
        if self.arc == "one_hot":
            leaf, init = "embedding", nn.linear.default_embed_init
        else:
            leaf, init = "kernel", nn.linear.default_kernel_init
        return self.param(leaf, init, (self.rows, self.width))


_LANES = 128  # columns of one tile of `_field_rows`: the lane width of a TPU vector register


def _field_rows(fields, values, dtype):
    """``[..., columns]``: each entity's row of the reference's concat, in the
    fields' order: the one-hot of the clamped id (``ops.one_hot``'s clamp), the
    position's bits high bit first (``ops.binary_encode``), the raw float.

    Written a lane tile of columns at a time, from the fields that reach into
    the tile, and not a field at a time: XLA writes such tiles straight into the
    matrix, where it writes 36 pieces of 1 to 327 columns apart and then copies
    them together (value and gradient of the embedding at 196,608 rows: 6.1
    against 10.9 ms on the v5e, PR 26).
    """
    spans, end = [], 0
    for (_key, arc, n), v in zip(fields, values):
        if arc == "one_hot":
            v = jnp.clip(v.astype(jnp.int32), 0, n - 1)
        elif arc == "binary":
            v = v.astype(jnp.int32)
        elif arc == "float":
            v = v.astype(jnp.float32)
        else:
            raise NotImplementedError(arc)
        start, end = end, end + (1 if arc == "float" else n)
        spans.append((start, end, arc, n, v[..., None]))
    tiles = []
    for lo in range(0, end, _LANES):
        hi = min(lo + _LANES, end)
        columns = jnp.arange(lo, hi)
        hot, raw = False, 0.0  # fields share no column: at most one term is not zero
        for start, stop, arc, n, v in spans:
            if stop <= lo or start >= hi:
                continue
            k = columns - start  # the column's place in this field
            if arc == "one_hot":
                hot |= v == k
            elif arc == "binary":
                bit = (v >> jnp.clip(n - 1 - k, 0, n - 1)) & 1
                hot |= (bit == 1) & (k >= 0) & (k < n)
            else:
                raw += jnp.where(k == 0, v, 0.0)
        tiles.append(jnp.where(hot, 1.0, raw).astype(dtype))
    return jnp.concatenate(tiles, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _project_fields(fields, dtype, values, table):
    """``rows(values) @ table`` in ``dtype`` with float32 accumulation.

    The backward pass is the one product ``rows^T x g`` and builds the rows
    again from ``values`` (the observations; with the table, which is a
    parameter, the only residuals), so the ``[..., columns]`` matrix never
    lives from the forward to the backward pass. ``values`` are data: the
    integer fields have no gradient, a ``float`` field's is ``g`` times its one
    row of the table, as ``nn.Dense`` gave it (a program that asks for none
    drops that product). Reverse mode only: a ``custom_vjp`` has no jvp rule,
    so forward-mode differentiation through the encoder raises.
    """
    rows = _field_rows(fields, values, dtype)
    out = jnp.einsum("...c,cw->...w", rows, table.astype(dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(dtype)


def _project_fields_fwd(fields, dtype, values, table):
    return _project_fields(fields, dtype, values, table), (values, table)


def _project_fields_bwd(fields, dtype, residuals, g):
    values, table = residuals
    # the barrier ties the rebuilt rows to `g`: without it XLA finds them equal
    # to the forward pass's and keeps those instead (392 MB at 6 x 64 frames,
    # through the whole step), as it would under `jax.checkpoint` without its own
    values, g = jax.lax.optimization_barrier((values, g))
    g = g.astype(dtype)
    rows = _field_rows(fields, values, dtype)
    # float32 like the leaves it is split into: accumulated over every entity
    # of the batch and never rounded to the compute dtype
    d_table = jnp.einsum("...c,...w->cw", rows, g, preferred_element_type=jnp.float32)
    d_values, column = [], 0
    for (_key, arc, n), v in zip(fields, values):
        if not jnp.issubdtype(v.dtype, jnp.floating):
            d_values.append(np.zeros(v.shape, jax.dtypes.float0))
        elif arc == "float":  # the column holds the observation itself
            d = jnp.einsum("...w,w->...", g, table[column].astype(dtype),
                           preferred_element_type=jnp.float32)
            d_values.append(d.astype(v.dtype))
        else:  # an id that came as a float: cast to an integer, so no gradient
            d_values.append(jnp.zeros_like(v))
        column += 1 if arc == "float" else n
    return tuple(d_values), d_table


_project_fields.defvjp(_project_fields_fwd, _project_fields_bwd)


def _field_sum_embed(mdl_prefix: str, fields, x: Dict[str, jnp.ndarray], width: int, dtype):
    """Sum of per-field projections into ``width``, as concat -> Dense: the
    fields' leaves stacked along rows make the reference's one matrix."""
    fields = tuple(tuple(f) for f in fields)
    table = jnp.concatenate([
        _FieldTable(n if arc != "float" else 1, width, arc, name=f"{mdl_prefix}_{key}")()
        for key, arc, n in fields
    ])
    return _project_fields(fields, dtype, tuple(x[key] for key, _, _ in fields), table)


class BeginningBuildOrderEncoder(nn.Module):
    """Transformer over the 20-slot build-order sequence with positional
    one-hot and binary-encoded (x, y) of each order location
    (role of reference scalar_encoder.py:19-53)."""

    action_num: int
    binary_dim: int = 10
    head_dim: int = 8
    output_dim: int = 64
    spatial_x: int = 160
    dtype: object = jnp.float32

    @nn.compact
    def __call__(self, bo: jnp.ndarray, bo_location: jnp.ndarray):
        B, L = bo.shape
        a = one_hot(bo, self.action_num)
        pos = jnp.broadcast_to(jnp.eye(L, dtype=jnp.float32)[None], (B, L, L))
        loc_x = binary_encode(bo_location.astype(jnp.int32) % self.spatial_x, self.binary_dim)
        loc_y = binary_encode(bo_location.astype(jnp.int32) // self.spatial_x, self.binary_dim)
        x = jnp.concatenate([a, pos, loc_x, loc_y], axis=-1)
        x = Transformer(
            head_dim=self.head_dim,
            hidden_dim=self.output_dim * 2,
            output_dim=self.output_dim,
            head_num=2,
            mlp_num=2,
            layer_num=3,
            ln_type="pre",
            dtype=self.dtype,
        )(x)
        x = x.mean(axis=1)
        return FCBlock(self.output_dim, "relu", dtype=self.dtype)(x)


class ScalarEncoder(nn.Module):
    """Per-field scalar embeddings -> (embedded_scalar, scalar_context,
    baseline_feature) triple (role of reference scalar_encoder.py:56-132).
    Output layout: concat of field outputs in config order, then the sin/cos
    time embedding last."""

    cfg: dict  # model config Config

    @nn.compact
    def __call__(self, x: Dict[str, jnp.ndarray]):
        sc = static_cfg(self.cfg).encoder.scalar
        outs, ctx, base = [], [], []
        for key, arc, n, out_dim, is_ctx, is_base in sc.fields:
            if arc == "time":
                continue
            if arc == "one_hot":
                v = jnp.clip(x[key].astype(jnp.int32), 0, n - 1)
                emb = jax.nn.relu(nn.Embed(n, out_dim, dtype=cdtype(self.cfg), name=f"embed_{key}")(v))
            elif arc == "fc":
                emb = FCBlock(out_dim, "relu", dtype=cdtype(self.cfg), name=f"fc_{key}")(
                    x[key].astype(jnp.float32)
                )
            elif arc == "bo_transformer":
                emb = BeginningBuildOrderEncoder(
                    action_num=sc.bo.action_num,
                    binary_dim=sc.bo.binary_dim,
                    head_dim=sc.bo.head_dim,
                    output_dim=sc.bo.output_dim,
                    spatial_x=static_cfg(self.cfg).spatial_x,
                    dtype=cdtype(self.cfg),
                    name="bo_encoder",
                )(x[key].astype(jnp.float32), x["bo_location"].astype(jnp.int32))
            else:
                raise NotImplementedError(arc)
            outs.append(emb)
            if is_ctx:
                ctx.append(emb)
            if is_base:
                base.append(emb)
        outs.append(self._time_embedding(x["time"].astype(jnp.float32)))
        return (
            jnp.concatenate(outs, axis=-1),
            jnp.concatenate(ctx, axis=-1),
            jnp.concatenate(base, axis=-1),
        )

    def _time_embedding(self, t: jnp.ndarray, dim: int = 32):
        idx = jnp.arange(dim, dtype=jnp.float32)
        denom = 1.0 / jnp.power(10000.0, (idx // 2 * 2) / dim)
        ang = t[:, None] * denom[None, :]
        even = jnp.sin(ang)
        odd = jnp.cos(ang)
        return jnp.where((jnp.arange(dim) % 2 == 0)[None, :], even, odd)


class SpatialEncoder(nn.Module):
    """One-hot planes + effect scatters + entity scatter_map -> conv stack.

    Returns (embedded_spatial [B, fc_dim], map_skip pyramid list) — the skip
    list feeds LocationHead (role of reference spatial_encoder.py:51-90;
    downsample 'maxpool', head 'fc', norm none per the default config).
    """

    cfg: dict

    @nn.compact
    def __call__(self, x: Dict[str, jnp.ndarray], scatter_map: jnp.ndarray):
        sp = static_cfg(self.cfg).encoder.spatial
        H, W = static_cfg(self.cfg).spatial_y, static_cfg(self.cfg).spatial_x
        planes = []
        for key, arc, n in sp.fields:
            v = x[key]
            if arc == "float":
                planes.append(v.astype(jnp.float32)[..., None] / 256.0)
            elif arc == "one_hot":
                planes.append(one_hot(v, n))
            elif arc == "scatter":
                # v: [B, EFFECT_LEN] flat indices into H*W
                B, L = v.shape
                idx = jnp.clip(v.astype(jnp.int32), 0, H * W - 1)
                plane = jnp.zeros((B, H * W), jnp.float32)
                plane = plane.at[jnp.arange(B)[:, None], idx].set(1.0)
                planes.append(plane.reshape(B, H, W, 1))
            else:
                raise NotImplementedError(arc)
        planes.append(scatter_map)
        h = jnp.concatenate(planes, axis=-1)
        h = Conv2DBlock(sp.project_dim, 1, 1, "SAME", "relu", dtype=cdtype(self.cfg))(h)
        map_skip: List[jnp.ndarray] = []
        for ch in sp.down_channels:
            map_skip.append(h)
            h = nn.max_pool(h, (2, 2), strides=(2, 2))
            h = Conv2DBlock(ch, 3, 1, "SAME", "relu", dtype=cdtype(self.cfg))(h)
        for _ in range(sp.resblock_num):
            map_skip.append(h)
            h = ResBlock(h.shape[-1], "relu", dtype=cdtype(self.cfg))(h)
        h = h.reshape(h.shape[0], -1)
        h = FCBlock(sp.fc_dim, "relu", dtype=cdtype(self.cfg))(h)
        return h, map_skip


class EntityEncoder(nn.Module):
    """997-wide field rows x one matrix -> 3-layer set transformer ->
    per-entity embeddings + masked-mean pooled embedding
    (role of reference entity_encoder.py:20-96)."""

    cfg: dict

    @nn.compact
    def __call__(self, x: Dict[str, jnp.ndarray], entity_num: jnp.ndarray):
        ent = static_cfg(self.cfg).encoder.entity
        width = ent.output_dim
        # the reference's concat(one-hots, bits, floats) @ W_embed, W_embed held
        # as one leaf a field
        h = _field_sum_embed("ent", ent.fields, x, width, cdtype(self.cfg))
        bias = self.param("ent_embed_bias", nn.initializers.zeros_init(), (width,))
        h = jax.nn.relu(h + bias)
        mask = sequence_mask(entity_num, h.shape[1])
        # transformer layers only (embedding fc already applied above);
        # remat recomputes each layer in the backward instead of keeping its
        # [B*T, 512, C] activations live (model cfg `remat`)
        layer_cls = (
            nn.remat(TransformerLayer)
            if static_cfg(self.cfg).get("remat", False)
            else TransformerLayer
        )
        for i in range(ent.layer_num):
            h = layer_cls(
                ent.head_dim,
                ent.hidden_dim,
                ent.output_dim,
                ent.head_num,
                ent.mlp_num,
                "relu",
                ent.ln_type,
                cdtype(self.cfg),
                attn_impl=ent.get("attention_impl", "xla"),
                # explicit name: params stay loadable across the remat toggle
                # (nn.remat's auto-name prefix would otherwise differ)
                name=f"TransformerLayer_{i}",
            )(h, mask)
        # the reference's build_activation returns an INPLACE ReLU, so its
        # `entity_fc(act(x))` also rewrites x before the pooling branch
        # (entity_encoder.py:82-96 + activation.py:85) — the pooled embedding
        # therefore reduces relu(x), and so do we (golden-parity verified)
        h = jax.nn.relu(h)
        entity_embeddings = FCBlock(width, "relu", dtype=cdtype(self.cfg), name="entity_fc")(h)
        reduce_type = static_cfg(self.cfg).entity_reduce_type
        masked = h * mask[..., None]
        if reduce_type in ("entity_num", "selected_units_num"):
            pooled = masked.sum(axis=1) / jnp.maximum(entity_num, 1)[:, None]
        elif reduce_type == "constant":
            pooled = masked.sum(axis=1) / 512.0
        elif reduce_type == "attention_pool":
            pooled = AttentionPool(head_num=2, output_dim=width, dtype=cdtype(self.cfg))(
                h, mask=mask[..., None]
            )
        else:
            raise NotImplementedError(reduce_type)
        embedded_entity = FCBlock(width, "relu", dtype=cdtype(self.cfg), name="embed_fc")(pooled)
        return entity_embeddings, embedded_entity, mask


class ValueEncoder(nn.Module):
    """Centralized-critic feature encoder over opponent stats and both sides'
    unit scatter maps (role of reference value_encoder.py:12-77).

    Expects a value_feature dict with keys: the configured fc fields,
    unit_alliance/unit_type/unit_x/unit_y/total_unit_count per unit,
    own_units_spatial/enemy_units_spatial [B,H,W] {0,1} maps, and
    enemy beginning_order/bo_location.
    """

    cfg: dict

    @nn.compact
    def __call__(self, x: Dict[str, jnp.ndarray]):
        vc = static_cfg(self.cfg).value.encoder
        fc_parts = [
            FCBlock(out, "relu", dtype=cdtype(self.cfg), name=f"fc_{key}")(x[key].astype(jnp.float32))
            for key, _in, out in vc.fc_fields
        ]
        unit_emb = None
        for key, n, dim in vc.unit_fields:
            e = nn.Embed(n, dim, dtype=cdtype(self.cfg), name=f"embed_{key}")(
                jnp.clip(x[key].astype(jnp.int32), 0, n - 1)
            )
            unit_emb = e if unit_emb is None else jnp.concatenate([unit_emb, e], axis=-1)
        proj = FCBlock(vc.scatter_dim, "relu", dtype=cdtype(self.cfg), name="scatter_project")(unit_emb)
        unit_mask = sequence_mask(x["total_unit_count"], proj.shape[1])
        proj = proj * unit_mask[..., None]
        loc = jnp.stack([x["unit_x"].astype(jnp.int32), x["unit_y"].astype(jnp.int32)], axis=-1)
        H, W = x["own_units_spatial"].shape[-2:]
        smap = scatter_connection(
            proj, loc, (H, W), "add",
            impl=static_cfg(self.cfg).encoder.scatter.get("impl", "product"),
        )
        spatial = jnp.concatenate(
            [
                smap,
                x["own_units_spatial"].astype(jnp.float32)[..., None],
                x["enemy_units_spatial"].astype(jnp.float32)[..., None],
            ],
            axis=-1,
        )
        h = Conv2DBlock(vc.spatial.project_dim, 1, 1, "SAME", "relu", dtype=cdtype(self.cfg))(spatial)
        for ch in vc.spatial.down_channels:
            h = nn.max_pool(h, (2, 2), strides=(2, 2))
            h = Conv2DBlock(ch, 3, 1, "SAME", "relu", dtype=cdtype(self.cfg))(h)
        for _ in range(vc.spatial.resblock_num):
            h = ResBlock(h.shape[-1], "relu", dtype=cdtype(self.cfg))(h)
        h = FCBlock(vc.spatial.fc_dim, "relu", dtype=cdtype(self.cfg), name="spatial_fc")(
            h.reshape(h.shape[0], -1)
        )
        bo = BeginningBuildOrderEncoder(
            action_num=vc.bo.action_num,
            binary_dim=vc.bo.binary_dim,
            head_dim=vc.bo.head_dim,
            output_dim=vc.bo.output_dim,
            spatial_x=static_cfg(self.cfg).spatial_x,
            dtype=cdtype(self.cfg),
            name="bo_encoder",
        )(x["beginning_order"].astype(jnp.float32), x["bo_location"].astype(jnp.int32))
        return jnp.concatenate(fc_parts + [h, bo], axis=-1)
