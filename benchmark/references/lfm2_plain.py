"""LFM2 written out plainly: forward pass, loss and gradients in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, from the
published equations (https://huggingface.co/LiquidAI/LFM2-24B-A2B,
``lfm2_moe``). It imports nothing of the program's model or operator code;
it reads the program's parameter tree (the same seed gives the same weights)
and its model config, and follows the same cut: the router scores all
``num_experts``, a position's weights are normalised over all
``num_experts_per_tok`` picks, and of the picks only the experts in
``experts_held`` add to the result.

  decoder layer    h = x + Op(RMSNorm(x)),  y = h + FF(RMSNorm(h))
  Op, conv         [B, C, X] = split3(W_in u); z = B * X;
                   c_t = sum_k w_k z_{t-2+k} (zeros before 0); W_out (C * c)
  Op, attention    q, k RMSNorm per head, RoPE (rotate-half, theta 1e6),
                   causal softmax(q k^T / sqrt(64)) v, a key/value head for
                   every 4 query heads; by query blocks, keys up to the block
  FF, dense        W_2 (silu(W_1 u) * W_3 u)
  FF, experts      s = sigmoid(W_g u); sel = top4(s + b);
                   w_e = s_e / (sum_sel s + 1e-6);
                   sum over e in sel that is held of w_e E_e(u): a loop over
                   the held experts, each over all positions, times a mask
  output           RMSNorm, logits = h W_emb^T; mean next-token cross-entropy

Departures from the published model are the configuration's (``assumed`` in
``benchmark/configs/lfm2_24b_a2b_ep8_l5.json``): head size 64, tied
embedding, a fixed ``expert_bias``, no auxiliary loss.

``products_in`` rounds both operands of every matrix product to a narrower
dtype first: how far a run in that precision would part from this one
(``float8_e4m3fn`` is the precision below the configuration's bfloat16).
"""
from __future__ import annotations

import functools
import time
from typing import Dict, Optional

QUERY_BLOCK = 512
GRADS_UP_TO_POSITIONS = 4096  # gradients cost three forwards: beside a set-up only at small sizes


@functools.lru_cache(None)
def _compiled(fn, static=()):
    """``fn`` compiled as one function, so that XLA fuses the passes over its
    large tensors (a softmax operation by operation is three times as slow on
    the CPU), where the caller has not compiled the whole pass already
    (``first_step``). A backward pass keeps the piece's arguments and computes
    the rest again: ``gradients`` goes piece by piece, because compiled as one
    the CPU backend holds every query block's scores at once (over 40 GB at
    8,192 positions)."""
    import jax

    def piece(*args, **fixed):
        return jax.checkpoint(functools.partial(fn, **fixed))(*args)

    return jax.jit(piece, static_argnames=static)


def _mm(a, b, spec: str, products_in):
    import jax
    import jax.numpy as jnp

    if products_in is not None:
        # rounded on the way in; a gradient passes the rounding as it came (a cast's own
        # transpose would round the cotangent too, and float8 holds nothing below 2e-3)
        rounded = lambda t: t + jax.lax.stop_gradient(t.astype(products_in).astype(jnp.float32) - t)
        a, b = rounded(a), rounded(b)
    return jnp.einsum(spec, a, b)


def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def short_conv(p, u, products_in):
    import jax.numpy as jnp

    S = u.shape[1]
    gate_b, gate_c, x = jnp.split(_mm(u, p["in_proj"]["kernel"], "bsd,de->bse", products_in), 3, axis=-1)
    z = gate_b * x
    w = p["conv_kernel"]                                        # [L, d]
    L = w.shape[0]
    z0 = jnp.concatenate([jnp.zeros_like(z[:, :L - 1]), z], axis=1)
    c = sum(w[k] * z0[:, k:k + S] for k in range(L))
    return _mm(gate_c * c, p["out_proj"]["kernel"], "bsd,de->bse", products_in)


def rotary(x, theta):
    """``x`` [S, H, D]: x * cos + rotate_half(x) * sin."""
    import jax.numpy as jnp

    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2) / D)
    angle = jnp.arange(S)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
    return x * cos + half * sin


def attention_block(qb, kb, vb, lo, products_in):
    """The queries ``lo``.. of every head [H, block, D] against the keys and
    values up to their block's end [H, hi, D]."""
    import jax
    import jax.numpy as jnp

    hi, D = kb.shape[1], qb.shape[-1]
    score = _mm(qb, kb, "hqd,hkd->hqk", products_in) / D ** 0.5
    seen = jnp.arange(lo, lo + qb.shape[1])[:, None] >= jnp.arange(hi)[None, :]
    prob = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf), axis=-1)
    return _mm(prob, vb, "hqk,hkd->hqd", products_in)


def attention(p, u, H, Hkv, D, eps, theta, products_in):
    """``H`` query heads over ``Hkv`` key/value heads of size ``D``."""
    import jax.numpy as jnp

    B, S, _ = u.shape
    out = []
    for b in range(B):                                           # one sequence at a time
        q = _mm(u[b], p["q_proj"]["kernel"], "sd,de->se", products_in).reshape(S, H, D)
        k = _mm(u[b], p["k_proj"]["kernel"], "sd,de->se", products_in).reshape(S, Hkv, D)
        v = _mm(u[b], p["v_proj"]["kernel"], "sd,de->se", products_in).reshape(S, Hkv, D)
        q = rotary(rms_norm(q, p["q_norm"]["scale"], eps), theta)
        k = rotary(rms_norm(k, p["k_norm"]["scale"], eps), theta)
        k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))  # head h reads key/value head h // 4
        q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))       # [H, S, D]: a product per head
        rows = []
        for lo in range(0, S, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, S)
            rows.append(_compiled(attention_block, ("lo", "products_in"))(
                q[:, lo:hi], k[:, :hi], v[:, :hi], lo=lo, products_in=products_in))
        out.append(jnp.concatenate(rows, axis=1).transpose(1, 0, 2).reshape(S, H * D))
    return _mm(jnp.stack(out), p["o_proj"]["kernel"], "bsd,de->bse", products_in)


def swiglu(u, w1, w2, w3, products_in):
    import jax

    return _mm(jax.nn.silu(_mm(u, w1, "nd,df->nf", products_in)) * _mm(u, w3, "nd,df->nf", products_in),
               w2, "nf,fd->nd", products_in)


def weighted_expert(u, w, w1, w2, w3, products_in):
    """``w_e E_e(u)`` with ``w`` [N, 1]: zero where the position did not pick the expert."""
    return w * swiglu(u, w1, w2, w3, products_in)


def router(p, bias, u, cfg):
    """``u`` [N, d] -> (scores [N, num_experts], the picks as a mask of k ones a row)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", u, p["router"]))  # the router is never rounded
    biased = s + bias if cfg["use_expert_bias"] else s
    kth = jnp.sort(biased, axis=-1)[:, -cfg["num_experts_per_tok"]][:, None]
    return s, (biased >= kth).astype(jnp.float32)


def experts_held(p, bias, u, cfg, products_in, chosen=None):
    """``u`` [N, d] -> (FF(u) over the experts held, rows routed to each, the
    router's own picks). ``chosen`` puts given picks in the place of the
    router's own."""
    import jax.numpy as jnp

    held = cfg["experts_held"]
    s, own = router(p, bias, u, cfg)
    chosen = own if chosen is None else chosen
    w = cfg["routed_scaling_factor"] * s * chosen / ((s * chosen).sum(-1, keepdims=True) + 1e-6)
    out = jnp.zeros_like(u)
    for j in range(held["count"]):
        e = held["offset"] + j
        out = out + _compiled(weighted_expert, ("products_in",))(
            u, w[:, e:e + 1], p["w1"][j], p["w2"][j], p["w3"][j], products_in=products_in)
    return out, chosen[:, held["offset"]:held["offset"] + held["count"]].sum(0), own


def forward(variables, cfg, tokens, products_in=None, picks=None):
    """``tokens`` [B, S] -> (logits [B, S, V], stats as the program reports
    them: ``rms``, ``ff_rms`` per layer, ``rows`` per expert layer, and the
    router's own ``picks`` per expert layer). ``picks`` (layer index -> mask
    [B*S, num_experts]) routes by given picks instead."""
    import jax.numpy as jnp

    params, buffers = variables["params"], variables.get("buffers", {})
    eps = cfg["norm_eps"]
    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    x = params["embedding"][tokens]
    B, S, d = x.shape
    stats = {"rms": [], "ff_rms": [], "rows": [], "picks": []}
    for i, kind in enumerate(cfg["layer_types"]):
        p = params[f"layer_{i}"]
        u = rms_norm(x, p["operator_norm"]["scale"], eps)
        if kind == "conv":
            x = x + _compiled(short_conv, ("products_in",))(p["short_conv"], u, products_in=products_in)
        else:
            x = x + attention(p["attention"], u, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                              cfg["head_dim"], eps, cfg["rope_theta"], products_in)
        if i < cfg["num_dense_layers"]:
            u = rms_norm(x, p["ffn_norm"]["scale"], eps).reshape(B * S, d)
            m = p["dense_mlp"]
            ff = _compiled(swiglu, ("products_in",))(
                u, m["w1"]["kernel"], m["w2"]["kernel"], m["w3"]["kernel"], products_in=products_in)
        else:
            u = rms_norm(x, p["moe"]["norm"]["scale"], eps).reshape(B * S, d)
            ff, rows, own = experts_held(p["moe"], buffers[f"layer_{i}"]["moe"]["expert_bias"], u, cfg,
                                         products_in, None if picks is None else picks[i])
            stats["rows"].append(rows)
            stats["picks"].append(own)
        x = x + ff.reshape(B, S, d)
        stats["rms"].append(rms(x))
        stats["ff_rms"].append(rms(ff))
    h = rms_norm(x, params["final_norm"]["scale"], eps)
    return _mm(h, params["embedding"], "bsd,vd->bsv", products_in), stats


def loss(params, variables, cfg, tokens, labels, products_in=None, picks=None):
    """Mean next-token cross-entropy over every position, and the stats."""
    import jax
    import jax.numpy as jnp

    logits, stats = forward({**variables, "params": params}, cfg, tokens, products_in, picks)
    log_p = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(log_p, labels[..., None], axis=-1)), (logits, stats)


def plain_config(model_cfg) -> Dict:
    """The program's model config as plain Python values."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim", "norm_eps", "rope_theta",
            "layer_types", "num_dense_layers", "num_experts_per_tok", "routed_scaling_factor",
            "use_expert_bias")
    cfg = {k: model_cfg[k] for k in keys}
    cfg["layer_types"] = list(cfg["layer_types"])
    cfg["experts_held"] = {k: int(model_cfg["experts_held"][k]) for k in ("offset", "count")}
    return cfg


def named(total, stats, cfg) -> Dict[str, float]:
    """The loss and the stats under the names of the learner's log."""
    out = {"total_loss": float(total), "moe_overflow_rows": 0.0}  # the loop leaves no pick out
    moe_layers = [i for i in range(len(cfg["layer_types"])) if i >= cfg["num_dense_layers"]]
    for i, (a, b) in enumerate(zip(stats["rms"], stats["ff_rms"])):
        out[f"residual_rms/layer_{i}"] = float(a)
        out[f"ff_rms/layer_{i}"] = float(b)
    for i, rows in zip(moe_layers, stats["rows"]):
        out.update({f"moe_rows/layer_{i}/expert_{e}": float(r) for e, r in enumerate(rows)})
    return out


def gradients(variables, cfg, tokens, labels, products_in: Optional[str] = None, picks=None):
    """The gradient of the batch's loss by every parameter, one sequence at a
    time (the batch's loss is the mean of its sequences' losses; ``picks``
    are then one sequence's)."""
    import jax

    total = None
    for b in range(tokens.shape[0]):
        g = jax.grad(lambda p: loss(p, variables, cfg, tokens[b:b + 1], labels[b:b + 1], products_in,
                                    picks)[0])(variables["params"])
        total = g if total is None else jax.tree.map(lambda x, y: x + y, total, g)
    return jax.tree.map(lambda x: x / tokens.shape[0], total)


def first_step(learner, batch, products_in: Optional[str] = None) -> Dict[str, float]:
    """The untrained weights on one batch: ``total_loss``, the rows routed to
    every held expert of every expert layer, the RMS of the residual stream
    and of the feed-forward output after every layer, and up to
    ``GRADS_UP_TO_POSITIONS`` positions the gradient norm of every top-level
    module (``dyn/grad_norm/<module>``, the names of the step's dynamics tree).

    One sequence at a time through one compiled function, so that the
    published widths at 8,192 positions fit beside a run's set-up (the run's
    machine has 40 GiB for both); the sequences' losses and mean squares
    average, their rows add."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = plain_config(learner.model_cfg)
    variables = learner.state["params"]
    tokens, labels = (jnp.asarray(np.asarray(batch[k]), jnp.int32) for k in ("tokens", "labels"))
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        @jax.jit
        def one(v, tok, lab):
            total, (_, stats) = loss(v["params"], v, cfg, tok, lab, products_in)
            return total, stats

        per_seq = [jax.device_get(one(variables, tokens[b:b + 1], labels[b:b + 1]))
                   for b in range(tokens.shape[0])]
        stats = {
            "rows": [sum(s["rows"][j] for _, s in per_seq) for j in range(len(per_seq[0][1]["rows"]))],
            **{k: [float(np.sqrt(np.mean([s[k][i] ** 2 for _, s in per_seq])))
                   for i in range(len(cfg["layer_types"]))] for k in ("rms", "ff_rms")},
        }
        out = named(np.mean([total for total, _ in per_seq]), stats, cfg)
        out["forward_seconds"] = time.perf_counter() - t
        if tokens.size <= GRADS_UP_TO_POSITIONS:
            for module, g in gradients(variables, cfg, tokens, labels, products_in).items():
                out[f"dyn/grad_norm/{module}"] = float(
                    jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
    return out
