"""``deepseek_v3`` (Kimi-VL-A3B-Instruct's language model) written out plainly:
forward pass, loss and gradients in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, from the published equations
(https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct, ``config.json``). It
imports nothing of the program's model or operator code; it reads the
program's parameter tree (the same seed gives the same weights) and its model
config, and follows the same cut: the router scores all ``n_routed_experts``,
a position's weights are normalised over all ``num_experts_per_tok`` picks, of
the picks only the experts in ``experts_held`` add to the result, and the
shared experts are whole.

  decoder layer    h = x + MLA(RMSNorm(x)),  y = h + FF(RMSNorm(h))
  MLA              q = W_q u, per head [q_nope 128 | q_pe 64] (no query latent);
                   [c 512 | k_pe 64] = W_kva u;
                   per head [k_nope 128 | v 128] = W_kvb RMSNorm(c);
                   q_pe, k_pe rotated: the pair (x_2j, x_2j+1) by the angle
                   t * theta^(-2j/64), AS WRITTEN (a stack of the two members);
                   k_h = [k_nope_h | k_pe], the one rotary key REPEATED per head;
                   causal softmax(q_h . k_h / sqrt(192)) v_h, a full 192-wide dot
                   product and a 128-wide value; by query blocks, keys up to
                   the block; W_o over the 16 x 128 outputs
  FF, dense        W_2 (silu(W_1 u) * W_3 u), width 11264
  FF, experts      s = sigmoid(W_r u); sel = top6(s + b);
                   w_e = 2.446 * s_e / (sum_sel s + 1e-20);
                   sum over e in sel that is held of w_e SwiGLU_e(u) (a loop over
                   the held experts, each over all positions, times a mask)
                   + SwiGLU_shared(u), width 2 x 1408
  output           RMSNorm, logits = h W_head; mean next-token cross-entropy

Departures from the published model are the configuration's (``assumed`` in
``benchmark/configs/kimi_vl_a3b_ep8_l6.json``): no vision tower, a fixed
``expert_bias``, no auxiliary loss. The published ``1e-20`` stands here; the
program's shared router divides by ``sum + 1e-6``.

A layer's attention is computed again in its backward pass (``jax.checkpoint``
around ``latent_attention``), which changes what is kept, not what is
computed: piece by piece, ``gradients`` would keep every query block's slice
of the keys and values of all six layers, 9 GB a sequence at the published
widths, beside the run it shares 40 GiB with.

``products_in`` rounds both operands of every matrix product to a narrower
dtype first: how far a run in that precision would part from this one
(``float8_e4m3fn`` is the precision below the configuration's bfloat16).
``without`` leaves one term of the equations out (``OMISSIONS``): what a
program with that fault would report, to show that the cell's limits see it.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

# what is the same in every plain model is written once, in the first of them: a piece compiled as one
# function, a product with rounded operands, RMSNorm, SwiGLU, the sigmoid router with its bias
from benchmark.references.lfm2_plain import _compiled, _mm, rms_norm, router, swiglu  # noqa: F401

QUERY_BLOCK = 512
GRADS_UP_TO_POSITIONS = 4096  # gradients cost three forwards: beside a set-up only at small sizes
OMISSIONS = ("rope",         # no rotation of the 64-wide part
             "k_pe",         # the shared rotary key zeroed
             "latent_norm",  # W_kvb c in place of W_kvb RMSNorm(c)
             "scale",        # 1/sqrt(128) in place of 1/sqrt(192)
             "shared",       # no shared experts
             "scaling")      # routed_scaling_factor left out


def rotary_interleaved(x, theta):
    """``x`` [S, H, R]: the pair (x_2j, x_2j+1) turned by ``t * theta^(-2j/R)``."""
    import jax.numpy as jnp

    S, _, R = x.shape
    angle = jnp.arange(S)[:, None] / theta ** (jnp.arange(0, R, 2) / R)[None, :]     # [S, R/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def attention_block(qb, kb, vb, lo, scale, products_in):
    """The queries ``lo``.. of every head [H, block, Dqk] against the keys
    [H, hi, Dqk] and values [H, hi, Dv] up to their block's end."""
    import jax
    import jax.numpy as jnp

    hi = kb.shape[1]
    score = _mm(qb, kb, "hqd,hkd->hqk", products_in) * scale
    seen = jnp.arange(lo, lo + qb.shape[1])[:, None] >= jnp.arange(hi)[None, :]
    prob = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf), axis=-1)
    return _mm(prob, vb, "hqk,hkd->hqd", products_in)


def latent_attention(p, u, cfg, products_in, without=()):
    """``u`` [B, S, d] -> MLA(u), one sequence at a time."""
    import jax.numpy as jnp

    B, S, _ = u.shape
    H, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    N, R, V = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    out = []
    for b in range(B):
        q = _mm(u[b], p["q_proj"]["kernel"], "sd,de->se", products_in).reshape(S, H, N + R)
        c_kpe = _mm(u[b], p["kv_a_proj"]["kernel"], "sd,de->se", products_in)
        c, k_pe = c_kpe[:, :rank], c_kpe[:, None, rank:]                      # [S, rank], [S, 1, R]
        if "latent_norm" not in without:
            c = rms_norm(c, p["kv_norm"]["scale"], eps)
        kv = _mm(c, p["kv_b_proj"]["kernel"], "sd,de->se", products_in).reshape(S, H, N + V)
        k_nope, v = kv[..., :N], kv[..., N:]
        q_nope, q_pe = q[..., :N], q[..., N:]
        if "rope" not in without:
            q_pe, k_pe = rotary_interleaved(q_pe, theta), rotary_interleaved(k_pe, theta)
        if "k_pe" in without:
            k_pe = jnp.zeros_like(k_pe)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([k_nope, jnp.repeat(k_pe, H, axis=1)], axis=-1)  # every head reads the one rotary key
        scale = (N if "scale" in without else N + R) ** -0.5
        q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))                   # [H, S, .]: a product per head
        rows = []
        for lo in range(0, S, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, S)
            rows.append(_compiled(attention_block, ("lo", "scale", "products_in"))(
                q[:, lo:hi], k[:, :hi], v[:, :hi], lo=lo, scale=scale, products_in=products_in))
        out.append(jnp.concatenate(rows, axis=1).transpose(1, 0, 2).reshape(S, H * V))
    return _mm(jnp.stack(out), p["o_proj"]["kernel"], "bsd,de->bse", products_in)


def weighted_expert(u, w, w1, w2, w3, products_in):
    """``w_e E_e(u)`` with ``w`` [N, 1]: zero where the position did not pick the expert."""
    return w * swiglu(u, w1, w2, w3, products_in)


def experts_held(p, bias, u, cfg, products_in, chosen=None, without=()):
    """``u`` [N, d] -> (FF(u) over the experts held plus the shared experts,
    rows routed to each held expert, the router's own picks). ``chosen`` puts
    given picks in the place of the router's own."""
    import jax.numpy as jnp

    held = cfg["experts_held"]
    s, own = router(p, bias, u, cfg)
    chosen = own if chosen is None else chosen
    scaling = 1.0 if "scaling" in without else cfg["routed_scaling_factor"]
    w = scaling * s * chosen / ((s * chosen).sum(-1, keepdims=True) + 1e-20)
    out = jnp.zeros_like(u)
    for j in range(held["count"]):
        e = held["offset"] + j
        out = out + _compiled(weighted_expert, ("products_in",))(
            u, w[:, e:e + 1], p["w1"][j], p["w2"][j], p["w3"][j], products_in=products_in)
    if "shared" not in without:
        out = out + _compiled(swiglu, ("products_in",))(
            u, p["shared_w1"], p["shared_w2"], p["shared_w3"], products_in=products_in)
    return out, chosen[:, held["offset"]:held["offset"] + held["count"]].sum(0), own


def forward(variables, cfg, tokens, products_in=None, picks=None, without=()):
    """``tokens`` [B, S] -> (logits [B, S, V], stats as the program reports
    them: ``rms``, ``attn_rms``, ``ff_rms`` per layer, ``rows`` per expert
    layer, and the router's own ``picks`` per expert layer). ``picks`` (layer
    index -> mask [B*S, n_routed_experts]) routes by given picks instead."""
    import jax
    import jax.numpy as jnp

    params, buffers = variables["params"], variables.get("buffers", {})
    eps = cfg["rms_norm_eps"]
    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    x = params["embedding"][tokens]
    B, S, d = x.shape
    stats = {"rms": [], "attn_rms": [], "ff_rms": [], "rows": [], "picks": []}
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        attn = jax.checkpoint(lambda mla, u: latent_attention(mla, u, cfg, products_in, without))(
            p["mla"], rms_norm(x, p["operator_norm"]["scale"], eps))
        x = x + attn
        if i < cfg["first_k_dense_replace"]:
            u = rms_norm(x, p["ffn_norm"]["scale"], eps).reshape(B * S, d)
            m = p["dense_mlp"]
            ff = _compiled(swiglu, ("products_in",))(
                u, m["w1"]["kernel"], m["w2"]["kernel"], m["w3"]["kernel"], products_in=products_in)
        else:
            u = rms_norm(x, p["moe"]["norm"]["scale"], eps).reshape(B * S, d)
            ff, rows, own = experts_held(p["moe"], buffers[f"layer_{i}"]["moe"]["expert_bias"], u, cfg,
                                         products_in, None if picks is None else picks[i], without)
            stats["rows"].append(rows)
            stats["picks"].append(own)
        x = x + ff.reshape(B, S, d)
        stats["rms"].append(rms(x))
        stats["attn_rms"].append(rms(attn))
        stats["ff_rms"].append(rms(ff))
    h = rms_norm(x, params["final_norm"]["scale"], eps)
    return _mm(h, params["lm_head"], "bsd,dv->bsv", products_in), stats


def loss(params, variables, cfg, tokens, labels, products_in=None, picks=None, without=()):
    """Mean next-token cross-entropy over every position, and the stats."""
    import jax
    import jax.numpy as jnp

    logits, stats = forward({**variables, "params": params}, cfg, tokens, products_in, picks, without)
    log_p = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(log_p, labels[..., None], axis=-1)), (logits, stats)


def plain_config(model_cfg) -> Dict:
    """The program's model config as plain Python values."""
    keys = ("num_hidden_layers", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rope_theta", "rms_norm_eps", "first_k_dense_replace", "num_experts_per_tok",
            "routed_scaling_factor", "use_expert_bias")
    cfg = {k: model_cfg[k] for k in keys}
    cfg["experts_held"] = {k: int(model_cfg["experts_held"][k]) for k in ("offset", "count")}
    return cfg


def named(total, stats, cfg) -> Dict[str, float]:
    """The loss and the stats under the names of the learner's log."""
    out = {"total_loss": float(total), "moe_overflow_rows": 0.0}  # the loop leaves no pick out
    for i, (a, b, c) in enumerate(zip(stats["rms"], stats["attn_rms"], stats["ff_rms"])):
        out[f"residual_rms/layer_{i}"] = float(a)
        out[f"attn_rms/layer_{i}"] = float(b)
        out[f"ff_rms/layer_{i}"] = float(c)
    for i, rows in zip(range(cfg["first_k_dense_replace"], cfg["num_hidden_layers"]), stats["rows"]):
        out.update({f"moe_rows/layer_{i}/expert_{e}": float(r) for e, r in enumerate(rows)})
        out[f"moe_rows_sum/layer_{i}"] = float(sum(float(r) for r in rows))
        out[f"moe_rows_max/layer_{i}"] = float(max(float(r) for r in rows))
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


def first_step(learner, batch, products_in: Optional[str] = None, without=()) -> Dict[str, float]:
    """The untrained weights on one batch: ``total_loss``, the rows routed to
    every held expert of every expert layer, the RMS of the residual stream,
    of the attention output and of the feed-forward output after every layer,
    and up to ``GRADS_UP_TO_POSITIONS`` positions the gradient norm of every
    top-level module (``dyn/grad_norm/<module>``, the names of the step's
    dynamics tree).

    One sequence at a time through one compiled function, so that the
    published widths at 8,192 positions fit beside a run's set-up; the
    sequences' losses and mean squares average, their rows add."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = plain_config(learner.model_cfg)
    variables = learner.state["params"]
    # the reference's process shares 40 GiB with the run: the Adam moments the learner made (8 bytes a
    # parameter) are read by nothing here
    learner._state = {"params": variables}
    tokens, labels = (jnp.asarray(np.asarray(batch[k]), jnp.int32) for k in ("tokens", "labels"))
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        @jax.jit
        def one(v, tok, lab):
            total, (_, stats) = loss(v["params"], v, cfg, tok, lab, products_in, None, without)
            return total, {k: s for k, s in stats.items() if k != "picks"}

        per_seq = [jax.device_get(one(variables, tokens[b:b + 1], labels[b:b + 1]))
                   for b in range(tokens.shape[0])]
        stats = {
            "rows": [sum(s["rows"][j] for _, s in per_seq) for j in range(len(per_seq[0][1]["rows"]))],
            **{k: [float(np.sqrt(np.mean([s[k][i] ** 2 for _, s in per_seq])))
                   for i in range(cfg["num_hidden_layers"])] for k in ("rms", "attn_rms", "ff_rms")},
        }
        out = named(np.mean([total for total, _ in per_seq]), stats, cfg)
        out["forward_seconds"] = time.perf_counter() - t
        if tokens.size <= GRADS_UP_TO_POSITIONS:
            for module, g in gradients(variables, cfg, tokens, labels, products_in).items():
                out[f"dyn/grad_norm/{module}"] = float(
                    jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
    return out
