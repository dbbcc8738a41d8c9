"""``laguna`` (Laguna-S-2.1's language model) written out plainly: forward
pass, loss and gradients in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, from the published equations
(https://huggingface.co/poolside/Laguna-S-2.1, ``config.json``, ``model_type:
laguna``). It imports nothing of the program's model or operator code; it
reads the program's parameter tree (the same seed gives the same weights) and
its model config, and follows the same cut: the heads built are the
``kv_heads_held`` key/value heads with their query heads, the router scores
all ``num_experts``, a position's weights are normalised over all
``num_experts_per_tok`` picks, of the picks only the experts in
``experts_held`` add to the result, and the shared expert is whole.

  decoder layer    h = x + Attn_i(RMSNorm(x)),  y = h + FF_i(RMSNorm(h))
  Attn_i           q = W_q u, k = W_k u, v = W_v u, g = sigmoid(W_g u) (one number
                   a head); q, k <- RMSNorm_128 per head; the rotation of
                   rope_parameters[layer_types[i]] over the first
                   partial_rotary_factor of each head, AS WRITTEN PAIRS: dimension
                   t with t + R/2, (a, b) -> (a cos - b sin, b cos + a sin), angle
                   pos * f_t. ``default``: f_t = theta^(-2t/R). ``yarn``: f_t (1 -
                   ramp_t) + f_t / factor * ramp_t, ramp_t = clip((t - low) / (high
                   - low), 0, 1), low = floor(R ln(L / (2 pi beta_fast)) / (2 ln
                   theta)), high = ceil(the same at beta_slow), L the original
                   length, and cos and sin times attention_factor.
                   softmax(q k^T / sqrt(128)) v over the keys j of query i with
                   (i - j >= 0), and in a sliding layer (i - j >= 0) & (i - j <
                   sliding_window), the mask written as that comparison of
                   positions; a key/value head for every group of query heads;
                   (g * head's output) W_o
  FF_i             dense: W_2 (silu(W_1 u) * W_3 u); sparse: p = softmax(W_r u) over
                   all 256; sel = top10(p); w_e = 2.5 p_e / sum_sel p; sum over e in
                   sel that is held of w_e SwiGLU_e(u) (a loop over the held
                   experts) + SwiGLU_shared(u)
  output           RMSNorm, logits = h W_head; mean next-token cross-entropy

Departures from the published description are the configuration's (``assumed``
in ``benchmark/configs/laguna_s_118b_ep32_tp2_l5.json``): a softmax router with
no selection bias, RMSNorm over each head of q and k, a shared expert without
a gate, the rotation pairing ``t`` with ``t + R/2``, no auxiliary loss and no
masking across documents. Two are this file's own, and change what is
multiplied, not what comes out. A block of ``QUERY_BLOCK`` queries is not
multiplied against all 16,384 keys: a full layer's block takes the keys up to
its own end (rounded up to a multiple of ``KEY_STEP``), a sliding layer's the keys from ``sliding_window`` rounded up to
whole blocks before its start (every key the comparison above can let through
for any query of the block: ``j <= i`` and ``j > i - sliding_window >= start -
sliding_window``), and the mask is that comparison over the positions of the
slice. ``keys="all"`` multiplies against every key instead (the tier-1 test
holds the two to each other). And an expert is computed over the rows that
picked it (``qwen3_next_plain.weighted_expert``).

How it is computed, not what: a block is ONE key/value head's group of query
heads over ``QUERY_BLOCK`` positions (the head's keys are read by its group,
not copied a query head), taken ``ROWS_AT_ONCE`` rows at a time so that a
group of rows' scores stay in a core's cache (a block's 200 MB of scores went
to memory six times). The blocks of a layer are independent and run
``BLOCKS_SIDE_BY_SIDE`` at a time on threads of their own. The attention
core's backward pass is written out block by block (``_core_backward``: a
block's pullback is JAX's, the sum over blocks is by hand), and ``gradients``
goes a layer at a time, so that the gradients of one sequence of 16,384
positions fit the gradient tool's child: 17.6 GB beside the tool's 15 GB in
the machine's 40 GiB, 277 s for the reference and 291 s for the control of
the child's 900 s (my CPU run, PR 38, 8 cores; one pullback over the whole
loss with blocks of every head at once held 28-31 GB and was killed there).

``products_in`` rounds both operands of every matrix product to a narrower
dtype first: how far a run in that precision would part from this one
(``float8_e4m3fn`` is the precision below the configuration's bfloat16).
``without`` leaves one term of the equations out or changes it (``OMISSIONS``):
what a program with that fault would report, to show that the cell's limits
see it.
"""
from __future__ import annotations

import functools
import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

# what is the same in every plain model is written once, in the first of them that has it: a piece compiled as
# one function, a product with rounded operands, RMSNorm, SwiGLU; an expert over the rows that picked it
from benchmark.references.lfm2_plain import _compiled, _mm, rms_norm, swiglu  # noqa: F401
from benchmark.references.qwen3_next_plain import weighted_expert  # noqa: F401

QUERY_BLOCK = 512
KEY_STEP = 2048  # a full layer's block takes keys up to a multiple of this: 8 shapes to compile at 16,384 positions, not 32
ROWS_AT_ONCE = 32  # rows of a block whose scores are computed together: 6 | 9 heads x 32 rows x 16,384 keys fit a core's cache
BLOCKS_SIDE_BY_SIDE = 8  # blocks computed at once, each on a thread of its own
GRADS_UP_TO_POSITIONS = 4096  # gradient norms beside a set-up only at small sizes (the rehearsal): at the cell's they are the gradient tool's
OMISSIONS = ("window",        # the sliding layers see every key before the query
             "window_1024",   # a window of twice the published one
             "window_minus_1",  # the off-by-one: i - j < sliding_window - 1
             "yarn",          # the full layers' rotation by the default table (their theta, their half of the head)
             "yarn_factor",   # YaRN's table without attention_factor on cos and sin
             "rope_whole",    # the whole head of a full layer rotated, not its first half
             "thetas",        # the two kinds' thetas swapped
             "gate",          # the heads' outputs without sigmoid(g)
             "scaling",       # moe_routed_scaling_factor left out
             "shared")        # no shared expert


def inv_freq(turn, rotary_dim, without=()):
    """The frequencies of one layer kind's rotation [rotary_dim / 2], from
    its published ``rope_parameters`` entry, as the formula writes them."""
    import jax.numpy as jnp

    t = jnp.arange(rotary_dim // 2, dtype=jnp.float32)
    f = 1.0 / turn["rope_theta"] ** (2.0 * t / rotary_dim)
    if turn["rope_type"] != "yarn" or "yarn" in without:
        return f
    edge = lambda beta: (rotary_dim * math.log(turn["original_max_position_embeddings"] / (2.0 * math.pi * beta))
                         / (2.0 * math.log(turn["rope_theta"])))
    low, high = max(math.floor(edge(turn["beta_fast"])), 0), min(math.ceil(edge(turn["beta_slow"])), rotary_dim - 1)
    ramp = jnp.clip((t - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - ramp) + f / turn["factor"] * ramp


def rotated(x, freq, factor):
    """``x`` [S, H, D]: the first ``2 len(freq)`` dimensions of each head as
    pairs (t, t + len(freq)) turned by ``pos * freq[t]``, cos and sin times
    ``factor``; the other dimensions as they are."""
    import jax.numpy as jnp

    half = freq.shape[0]
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]                # [S, R/2]
    cos, sin = factor * jnp.cos(angle)[:, None, :], factor * jnp.sin(angle)[:, None, :]
    a, b, kept = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, kept], axis=-1)


def attention_rows(qr, kb, vb, at, first, window, products_in):
    """The queries at positions ``at``.. of the query heads of ONE key/value
    head [G, rows, D] against that head's keys and values at positions
    ``first``.. [K, D]; the mask is the published comparison of a query's
    position with a key's."""
    import jax
    import jax.numpy as jnp

    score = _mm(qr, kb, "gqd,kd->gqk", products_in) / qr.shape[-1] ** 0.5
    i = at + jnp.arange(qr.shape[1])[:, None]
    j = first + jnp.arange(kb.shape[0])[None, :]
    seen = (i - j >= 0) if window is None else (i - j >= 0) & (i - j < window)
    prob = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf), axis=-1)
    return _mm(prob, vb, "gqk,kd->gqd", products_in)


def attention_block(qb, kb, vb, lo, first, window, products_in):
    """``attention_rows`` over a block of queries [G, block, D],
    ``ROWS_AT_ONCE`` rows at a time: the scores of a block against 16,384 keys
    are 200 MB and every pass over them (the mask, the softmax's three, the
    products') goes to memory; those of 32 rows stay in a core's cache. A
    backward pass computes a group of rows' scores again, as it does a block's."""
    import jax
    import jax.numpy as jnp

    G, block, D = qb.shape
    rows = min(block, ROWS_AT_ONCE)
    if block % rows:
        return attention_rows(qb, kb, vb, lo, first, window, products_in)
    out = jax.lax.map(
        jax.checkpoint(lambda of: attention_rows(of[0], kb, vb, of[1], first, window, products_in)),
        (qb.reshape(G, block // rows, rows, D).transpose(1, 0, 2, 3), lo + rows * jnp.arange(block // rows)))
    return out.transpose(1, 0, 2, 3).reshape(G, block, D)


def layer_window(cfg, kind: str, without=()) -> Optional[int]:
    if kind != "sliding_attention" or "window" in without:
        return None
    W = cfg["sliding_window"]
    return 2 * W if "window_1024" in without else W - 1 if "window_minus_1" in without else W


def spans(S: int, window: Optional[int], keys: str):
    """``(lo, hi, first, last)`` a block of queries: its positions and the
    positions of the keys it is multiplied against."""
    block = min(S, QUERY_BLOCK)
    before = 0 if window is None else -(-window // block) * block
    up_to = lambda hi: hi if window is not None else min(-(-hi // KEY_STEP) * KEY_STEP, S)
    return [(lo, min(lo + block, S), *((0, S) if keys == "all" else
                                      (0 if window is None else max(lo - before, 0), up_to(min(lo + block, S)))))
            for lo in range(0, S, block)]


def _side_by_side(one, items):
    """``one`` over ``items``, ``BLOCKS_SIDE_BY_SIDE`` at a time on threads of
    their own (a block's mask and softmax run on one core each: side by side
    they fill the host). The arrays are values: nothing here is traced as a whole."""
    with ThreadPoolExecutor(BLOCKS_SIDE_BY_SIDE) as pool:
        return list(pool.map(one, items))


def _blocks(q, window, keys):
    """``(key/value head, lo, hi, first, last)`` of every block of ``q`` [Hkv, G, S, D], a head's blocks together."""
    return [(h, *span) for h in range(q.shape[0]) for span in spans(q.shape[2], window, keys)]


def _joined(blocks, heads: int):
    """``_blocks``' [G, block, D] each -> [heads, G, S, D]."""
    import jax.numpy as jnp

    a_head = len(blocks) // heads
    return jnp.stack([jnp.concatenate(blocks[h * a_head:(h + 1) * a_head], axis=1) for h in range(heads)])


def _block_pullback(qb, kb, vb, lo, first, g, window, products_in):
    import jax

    return jax.vjp(lambda q, k, v: attention_block(q, k, v, lo, first, window, products_in), qb, kb, vb)[1](g)


def _core(q, k, v, window, keys, products_in):
    import jax.numpy as jnp

    block_of = _compiled(attention_block, ("window", "products_in"))
    one = lambda at: block_of(q[at[0], :, at[1]:at[2]], k[at[0], at[3]:at[4]], v[at[0], at[3]:at[4]],
                              jnp.int32(at[1]), jnp.int32(at[3]), window=window, products_in=products_in)
    return _joined(_side_by_side(one, _blocks(q, window, keys)), q.shape[0])


def _core_backward(window, keys, products_in, qkv, g):
    """The cotangents of q, k and v from the output's, block by block: a
    block's scores are computed again, and its share of dK and dV is added
    to the rows of its keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    q, k, v = qkv
    pull = jax.jit(_block_pullback, static_argnames=("window", "products_in"))
    one = lambda at: pull(q[at[0], :, at[1]:at[2]], k[at[0], at[3]:at[4]], v[at[0], at[3]:at[4]],
                          jnp.int32(at[1]), jnp.int32(at[3]), g[at[0], :, at[1]:at[2]], window=window, products_in=products_in)
    dk, dv = np.zeros(k.shape, np.float32), np.zeros(v.shape, np.float32)   # added to in place, a block at a time
    adding = threading.Lock()

    def one_added(block):
        dq_b, dk_b, dv_b = one(block)
        h, _, _, first, last = block
        with adding:
            dk[h, first:last] += np.asarray(dk_b)
            dv[h, first:last] += np.asarray(dv_b)
        return dq_b

    return _joined(_side_by_side(one_added, _blocks(q, window, keys)), q.shape[0]), jnp.asarray(dk), jnp.asarray(dv)


def attention_core(q, k, v, window, keys, products_in):
    """``q`` [Hkv, G, S, D], ``k``, ``v`` [Hkv, S, D] -> [Hkv, G, S, D]:
    every block of queries of every key/value head's group
    (``attention_block``) against the keys ``spans`` gives it. Its backward
    pass is written out (``_core_backward``): JAX's own keeps every block's
    slice of the keys and pads each block's cotangent to the whole sequence."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
    def core(q, k, v, window, keys, products_in):
        return _core(q, k, v, window, keys, products_in)

    core.defvjp(lambda q, k, v, *static: (_core(q, k, v, *static), (q, k, v)), _core_backward)
    return core(q, k, v, window, keys, products_in)


def heads_of(q_proj, k_proj, v_proj, g_proj, q_scale, k_scale, freq, u, H, Hkv, D, eps, factor, products_in):
    """One sequence ``u`` [S, d] -> q [Hkv, G, S, D] (query head ``h`` reads
    key/value head ``h // G``), k, v [Hkv, S, D] and the gates [S, H]."""
    import jax

    S = u.shape[0]
    q = _mm(u, q_proj, "sd,de->se", products_in).reshape(S, H, D)
    k = _mm(u, k_proj, "sd,de->se", products_in).reshape(S, Hkv, D)
    v = _mm(u, v_proj, "sd,de->se", products_in).reshape(S, Hkv, D)
    gate = jax.nn.sigmoid(_mm(u, g_proj, "sd,dh->sh", products_in))
    q, k = rotated(rms_norm(q, q_scale, eps), freq, factor), rotated(rms_norm(k, k_scale, eps), freq, factor)
    return q.reshape(S, Hkv, H // Hkv, D).transpose(1, 2, 0, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2), gate


def gated_out(heads, gate, o_proj, gated, products_in):
    """``heads`` [Hkv, G, S, D] -> ``(g * head's output) W_o`` [S, d]."""
    Hkv, G, S, D = heads.shape
    H, heads = Hkv * G, heads.transpose(2, 0, 1, 3).reshape(S, Hkv * G, D)
    return _mm((heads * gate[..., None] if gated else heads).reshape(S, H * D), o_proj, "sd,de->se", products_in)


def attention(p, u, cfg, index: int, products_in, without=(), keys: str = "band"):
    """``u`` [B, S, d] -> (the attention layer's output over the heads held,
    the mean of sigmoid(g)), one sequence at a time. The projections, norms
    and rotation of a sequence are one compiled piece, the blocks of queries
    (``attention_core``) the second, the gate and ``W_o`` a third: a backward
    pass keeps a piece's arguments and computes the rest again."""
    import jax.numpy as jnp

    B, S, _ = u.shape
    D, kind = cfg["head_dim"], cfg["layer_types"][index]
    Hkv = cfg["kv_heads_held"]["count"]
    H = cfg["num_attention_heads_per_layer"][index] // cfg["num_key_value_heads"] * Hkv
    turn = dict(cfg["rope_parameters"][kind])
    if "thetas" in without:
        other = "sliding_attention" if kind == "full_attention" else "full_attention"
        turn["rope_theta"] = cfg["rope_parameters"][other]["rope_theta"]
    R = D if "rope_whole" in without else int(D * turn["partial_rotary_factor"])
    freq = inv_freq(turn, R, without)
    factor = turn["attention_factor"] if turn["rope_type"] == "yarn" and not {"yarn", "yarn_factor"} & set(without) else 1.0
    window, eps = layer_window(cfg, kind, without), cfg["rms_norm_eps"]
    out, opened = [], []
    for b in range(B):
        q, k, v, gate = _compiled(heads_of, ("H", "Hkv", "D", "eps", "factor", "products_in"))(
            p["q_proj"]["kernel"], p["k_proj"]["kernel"], p["v_proj"]["kernel"], p["g_proj"]["kernel"],
            p["q_norm"]["scale"], p["k_norm"]["scale"], freq, u[b], H=H, Hkv=Hkv, D=D, eps=eps, factor=float(factor),
            products_in=products_in)
        opened.append(jnp.mean(gate))
        out.append(_compiled(gated_out, ("gated", "products_in"))(
            attention_core(q, k, v, window, keys, products_in), gate, p["o_proj"]["kernel"],
            gated="gate" not in without, products_in=products_in))
    return jnp.stack(out), jnp.mean(jnp.stack(opened))


def router(p, bias, u, cfg):
    """``u`` [N, d] -> (probabilities [N, num_experts], the picks as a mask of
    k ones a row). ``bias`` is the program's buffer, which a softmax router
    does not read."""
    import jax
    import jax.numpy as jnp

    prob = jax.nn.softmax(jnp.einsum("nd,de->ne", u, p["router"]), axis=-1)   # the router is never rounded
    kth = jax.lax.top_k(prob, cfg["num_experts_per_tok"])[0][:, -1:]
    return prob, (prob >= kth).astype(jnp.float32)


def experts_held(p, bias, u, cfg, products_in, chosen=None, without=()):
    """``u`` [N, d] -> (FF(u) over the experts held plus the shared expert,
    rows routed to each held expert, the router's own picks). ``chosen`` puts
    given picks in the place of the router's own."""
    import jax
    import jax.numpy as jnp

    held = cfg["experts_held"]
    mine = slice(held["offset"], held["offset"] + held["count"])
    prob, own = router(p, bias, u, cfg)
    chosen = own if chosen is None else chosen
    scaling = 1.0 if "scaling" in without else cfg["moe_routed_scaling_factor"]
    w = scaling * prob * chosen / (prob * chosen).sum(-1, keepdims=True)

    def add_expert(out, of):   # the loop over the held experts, its body compiled once
        w_e, chosen_e, w1, w2, w3 = of
        return out + _compiled(weighted_expert, ("products_in",))(
            u, w_e[:, None], chosen_e > 0, w1, w2, w3, products_in=products_in), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (w[:, mine].T, chosen[:, mine].T, p["w1"], p["w2"], p["w3"]))
    if "shared" not in without:
        out = out + _compiled(swiglu, ("products_in",))(
            u, p["shared_w1"], p["shared_w2"], p["shared_w3"], products_in=products_in)
    return out, chosen[:, mine].sum(0), own


def head_and_loss(x, scale, head, labels, eps, products_in):
    """The final norm, the logits over the rows held and the mean next-token cross-entropy."""
    import jax
    import jax.numpy as jnp

    logits = _mm(rms_norm(x, scale, eps), head, "bsd,dv->bsv", products_in)
    log_p = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(log_p, labels[..., None], axis=-1)), logits


def layer(p, x, cfg, i: int, products_in=None, chosen=None, without=(), keys: str = "band"):
    """Decoder layer ``i``: ``x`` [B, S, d] -> (``y``, its stats: ``rms``,
    ``mixer_rms``, ``ff_rms``, ``attn_gate_mean``, and of an expert layer
    ``rows`` and the router's own ``picks``). Every norm is a compiled piece
    of its own: a backward pass keeps the residual stream and not what a norm
    made of it."""
    import jax.numpy as jnp

    B, S, d = x.shape
    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    norm = lambda x, scale: _compiled(rms_norm, ("eps",))(x, scale, eps=cfg["rms_norm_eps"])
    mixed, opened = attention(p["attn"], norm(x, p["operator_norm"]["scale"]), cfg, i, products_in, without, keys)
    x, stats = x + mixed, {}
    if cfg["mlp_layer_types"][i] == "dense":
        u = norm(x, p["ffn_norm"]["scale"]).reshape(B * S, d)
        m = p["dense_mlp"]
        ff = _compiled(swiglu, ("products_in",))(
            u, m["w1"]["kernel"], m["w2"]["kernel"], m["w3"]["kernel"], products_in=products_in)
    else:
        u = norm(x, p["moe"]["norm"]["scale"]).reshape(B * S, d)
        ff, stats["rows"], stats["picks"] = experts_held(p["moe"], None, u, cfg, products_in, chosen, without)
    x = x + ff.reshape(B, S, d)
    return x, dict(stats, rms=rms(x), mixer_rms=rms(mixed), ff_rms=rms(ff), attn_gate_mean=opened)


def forward(variables, cfg, tokens, products_in=None, picks=None, without=(), keys: str = "band", labels=None):
    """``tokens`` [B, S] -> (logits [B, S, V], stats as the program reports
    them: ``rms``, ``mixer_rms``, ``ff_rms`` and ``attn_gate_mean`` per layer,
    ``rows`` per expert layer, and the router's own ``picks`` per expert
    layer; with ``labels`` the loss is ``stats["loss"]``). ``picks`` (layer
    index -> mask [B*S, num_experts]) routes by given picks instead. The
    buffers hold a selection bias, which a softmax router does not read."""
    import jax.numpy as jnp

    params = variables["params"]
    x = params["embedding"][tokens]
    stats = {k: [] for k in ("rms", "mixer_rms", "ff_rms", "attn_gate_mean", "rows", "picks")}
    for i in range(len(cfg["layer_types"])):
        x, of_layer = layer(params[f"layer_{i}"], x, cfg, i, products_in, None if picks is None else picks.get(i),
                            without, keys)
        for k, value in of_layer.items():
            stats[k].append(value)
    total, logits = _compiled(head_and_loss, ("eps", "products_in"))(
        x, params["final_norm"]["scale"], params["lm_head"], jnp.zeros(tokens.shape, jnp.int32) if labels is None else labels,
        eps=cfg["rms_norm_eps"], products_in=products_in)
    return logits, dict(stats, loss=total)


def loss(params, variables, cfg, tokens, labels, products_in=None, picks=None, without=(), keys: str = "band"):
    """Mean next-token cross-entropy over every position, and the stats."""
    logits, stats = forward({**variables, "params": params}, cfg, tokens, products_in, picks, without, keys, labels)
    return stats.pop("loss"), (logits, stats)


def plain_config(model_cfg) -> Dict:
    """The program's model config as plain Python values."""
    keys = ("head_dim", "num_key_value_heads", "sliding_window", "rms_norm_eps", "num_experts_per_tok",
            "moe_routed_scaling_factor")
    cfg = {k: model_cfg[k] for k in keys}
    cfg.update({k: list(model_cfg[k]) for k in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")})
    cfg["rope_parameters"] = {kind: dict(model_cfg["rope_parameters"][kind]) for kind in set(cfg["layer_types"])}
    cfg["experts_held"] = {n: int(model_cfg["experts_held"][n]) for n in ("offset", "count")}
    cfg["kv_heads_held"] = {"count": int(model_cfg["kv_heads_held"]["count"])}
    return cfg


def named(total, stats, cfg) -> Dict[str, float]:
    """The loss and the stats under the names of the learner's log."""
    out = {"total_loss": float(total), "moe_overflow_rows": 0.0}  # the loop leaves no pick out
    for i, (a, b, c, g) in enumerate(zip(stats["rms"], stats["mixer_rms"], stats["ff_rms"], stats["attn_gate_mean"])):
        out[f"residual_rms/layer_{i}"] = float(a)
        out[f"mixer_rms/layer_{i}"] = float(b)
        out[f"ff_rms/layer_{i}"] = float(c)
        out[f"attn_gate_mean/layer_{i}"] = float(g)
    sparse = [i for i, kind in enumerate(cfg["mlp_layer_types"]) if kind == "sparse"]
    for i, rows in zip(sparse, stats["rows"]):
        out.update({f"moe_rows/layer_{i}/expert_{e}": float(r) for e, r in enumerate(rows)})
        out[f"moe_rows_sum/layer_{i}"] = float(sum(float(r) for r in rows))
        out[f"moe_rows_max/layer_{i}"] = float(max(float(r) for r in rows))
    return out


def gradients(variables, cfg, tokens, labels, products_in: Optional[str] = None, picks=None):
    """The gradient of the batch's loss by every parameter, one sequence at a
    time (the batch's loss is the mean of its sequences' losses; ``picks``
    are then one sequence's) and, within it, A LAYER AT A TIME: the forward
    pass keeps each layer's input and nothing else, and the backward pass
    takes the layers from the last, each one's pullback (JAX's, of ``layer``)
    made when its turn comes and dropped when it has been used. One pullback
    over the whole loss holds every layer's pieces at once, which at 16,384
    positions and the published widths is more than the gradient tool's
    child has beside the tool. The pieces compiled as one function each
    (``_compiled``) are computed again in their backward pass."""
    import jax
    import jax.numpy as jnp

    params, eps, total = variables["params"], cfg["rms_norm_eps"], None
    layers = range(len(cfg["layer_types"]))
    a_layer = lambda i: lambda p, x: layer(p, x, cfg, i, products_in, None if picks is None else picks.get(i))[0]
    for b in range(tokens.shape[0]):
        ids, wanted = tokens[b:b + 1], labels[b:b + 1]
        x, entered = params["embedding"][ids], []
        for i in layers:
            entered.append(x)
            x = a_layer(i)(params[f"layer_{i}"], x)
        g = {"final_norm": {}}
        gx, g["final_norm"]["scale"], g["lm_head"] = jax.grad(
            lambda x, scale, head: _compiled(head_and_loss, ("eps", "products_in"))(
                x, scale, head, wanted, eps=eps, products_in=products_in)[0], argnums=(0, 1, 2))(
            x, params["final_norm"]["scale"], params["lm_head"])
        for i in reversed(layers):
            g[f"layer_{i}"], gx = jax.vjp(a_layer(i), params[f"layer_{i}"], entered.pop())[1](gx)
        g["embedding"] = jnp.zeros_like(params["embedding"]).at[ids].add(gx)
        total = g if total is None else jax.tree.map(lambda x, y: x + y, total, g)
    return jax.tree.map(lambda x: x / tokens.shape[0], total)


def first_step(learner, batch, products_in: Optional[str] = None, without=()) -> Dict[str, float]:
    """The untrained weights on one batch: ``total_loss``, the rows routed to
    every held expert of every expert layer, the RMS of the residual stream,
    of the attention output and of the feed-forward output after every layer,
    the mean gate of every layer, and up to ``GRADS_UP_TO_POSITIONS``
    positions the gradient norm of every top-level module
    (``dyn/grad_norm/<module>``, the names of the step's dynamics tree).

    A sequence a call, so that the published widths at 16,384 positions fit
    beside a run's set-up; the sequences' losses, means and mean squares
    average, their rows add."""
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
        per_seq = []
        for b in range(tokens.shape[0]):
            total, (_, stats) = loss(variables["params"], variables, cfg, tokens[b:b + 1], labels[b:b + 1],
                                     products_in, None, without)
            per_seq.append(jax.device_get((total, {k: s for k, s in stats.items() if k != "picks"})))
        over = lambda k: range(len(per_seq[0][1][k]))
        stats = {
            "rows": [sum(s["rows"][j] for _, s in per_seq) for j in over("rows")],
            **{k: [float(np.sqrt(np.mean([s[k][i] ** 2 for _, s in per_seq]))) for i in over(k)]
               for k in ("rms", "mixer_rms", "ff_rms")},
            "attn_gate_mean": [float(np.mean([s["attn_gate_mean"][i] for _, s in per_seq]))
                               for i in over("attn_gate_mean")],
        }
        out = named(np.mean([total for total, _ in per_seq]), stats, cfg)
        out["forward_seconds"] = time.perf_counter() - t
        if tokens.size <= GRADS_UP_TO_POSITIONS:
            out.update({f"dyn/grad_norm/{module}": float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
                        for module, g in gradients(variables, cfg, tokens, labels, products_in).items()})
    return out
