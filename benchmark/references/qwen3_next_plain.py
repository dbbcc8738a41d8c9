"""``qwen3_next`` (Qwen3-Next-80B-A3B-Instruct's language model) written out
plainly: forward pass, loss and gradients in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, from the published equations
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, ``config.json``,
``model_type: qwen3_next``). It imports nothing of the program's model or
operator code; it reads the program's parameter tree (the same seed gives the
same weights) and its model config, and follows the same cut: the router
scores all ``num_experts``, a position's weights are normalised over all
``num_experts_per_tok`` picks, of the picks only the experts in
``experts_held`` add to the result, and the shared expert is whole.

  Norm(x)          x / sqrt(mean(x^2) + eps) * (1 + w)
  decoder layer    h = x + Mixer_i(Norm(x)),  y = h + FF(Norm(h));  attention
                   where (i + 1) % full_attention_interval == 0
  Gated DeltaNet   [q k v z] = W_qkvz u; [b a] = W_ba u;
                   [q k v] <- silu(conv_4([q k v])), no bias;
                   q <- q / |q| / sqrt(128), k <- k / |k| per head (eps 1e-6
                   under the root); value head j reads q/k head j // 2;
                   beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias);
                   S'_t = exp(g_t) S_{t-1};
                   S_t = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T;  o_t = S_t^T q_t
                   AS WRITTEN: one ``lax.scan`` step a position, no chunks, no
                   triangular system, no carry;
                   y = o / sqrt(mean(o^2) + eps) * w_o * silu(z) per head; W_out y
  attention        [q gate] = W_q u, each head's 512 split into q and gate;
                   q, k <- Norm_256 per head; the first 64 of each head's 256
                   dimensions rotated (x cos + rotate_half(x) sin over those 64,
                   angle t * theta^(-2i/64)), the other 192 as they are; causal
                   softmax(q k^T / 16) v with a full 256-wide dot product, a
                   key/value head for every 8 query heads, by query blocks, keys
                   up to the block; out * sigmoid(gate); W_o
  FF               p = softmax(W_r u) over all 512; sel = top10(p);
                   w_e = p_e / sum_sel p; sum over e in sel that is held of
                   w_e SwiGLU_e(u) (a loop over the held experts, each over the
                   rows that picked it, or over all positions times a mask where
                   more than an eighth did) + sigmoid(u . w_g) SwiGLU_shared(u)
  output           Norm, logits = h W_head; mean next-token cross-entropy

Departures from the published model are the configuration's (``assumed`` in
``benchmark/configs/qwen3_next_80b_a3b_ep16_l4.json``): no multi-token
prediction head, no auxiliary loss, the columns of the fused projections in
the order ``[q | k | v | z]`` and ``[b | a]`` (the published code interleaves
them by key head; under random weights the order is free). The recurrence is
computed again block by block in its backward pass (``jax.checkpoint`` over
``REMAT_BLOCK`` positions; a state a position is 2 MB a layer) and so is each
mixer, which changes what is kept, not what is computed.

``products_in`` rounds both operands of every matrix product, and the
recurrence's ``q``, ``k`` and ``v``, to a narrower dtype first: how far a run
in that precision would part from this one (``float8_e4m3fn`` is the
precision below the configuration's bfloat16). ``without`` leaves one term of
the equations out (``OMISSIONS``): what a program with that fault would
report, to show that the cell's limits see it.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

# what is the same in every plain model is written once, in the first of them that has it: a piece compiled as
# one function, a product with rounded operands, a block of queries against the keys before it, SwiGLU; an
# operand rounded on its way into a recurrence, and one number for a layer's carried states
from benchmark.references.lfm2_plain import _compiled, _mm, attention_block, swiglu  # noqa: F401
from benchmark.references.nemotron_h_plain import _rounded, state_rms  # noqa: F401

QUERY_BLOCK = 512
REMAT_BLOCK = 256
GRADS_UP_TO_POSITIONS = 4096  # gradients cost three forwards: beside a set-up only at small sizes
ROUTED_SHARES = (32, 8)  # an expert picked by at most a 32nd, or an eighth, of the positions is computed over that many rows
OMISSIONS = ("decay",        # alpha = 1: the state never fades
             "beta",         # beta = 1: every write at full strength
             "carry",        # the state starts from zero every gdn_chunk_size positions
             "z_gate",       # Gated DeltaNet's output without silu(z)
             "l2norm",       # q and k as the convolution left them (q still over sqrt(128))
             "attn_gate",    # attention's output without sigmoid(gate)
             "rope",         # no rotation
             "rope_whole",   # the whole head rotated, not its first quarter
             "one_plus_w",   # Norm without the 1 +: with w at zero, nothing passes
             "shared_gate",  # the shared expert without its gate
             "shared")       # no shared expert


def norm(x, w, eps, without=()):
    """The family's zero-centred RMSNorm."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (w if "one_plus_w" in without else 1.0 + w)


def recurrence(q, k, v, g, beta, reset_every: int = 0):
    """The gated delta rule of one sequence, a step a position. ``q``/``k``
    [S, Hk, K], ``v`` [S, H, V], ``g``/``beta`` [S, H] -> (``o`` [S, H, V],
    the last state [H, K, V]). ``reset_every``: the fault of a chunked rule
    that drops its carry."""
    import jax
    import jax.numpy as jnp

    S, H, V = v.shape
    Hk, K = q.shape[1:]
    per_value_head = lambda t: jnp.repeat(t, H // Hk, axis=0)      # [Hk, K] -> [H, K]: head j reads q/k head j // (H / Hk)

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t, t = at
        if reset_every:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        q_t, k_t = per_value_head(q_t), per_value_head(k_t)
        state = jnp.exp(g_t)[:, None, None] * state
        error = v_t - jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + beta_t[:, None, None] * k_t[:, :, None] * error[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, ats):
        return jax.lax.scan(step, state, ats)

    size = min(REMAT_BLOCK, S)
    pad = -S % size                                                 # g = 0, beta = 0: the state stays, the rows are cut
    ats = tuple(jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(-1, size, *t.shape[1:])
                for t in (q, k, v, g, beta, jnp.arange(S)))
    last, o = jax.lax.scan(block, jnp.zeros((H, K, V), v.dtype), ats)
    return o.reshape(-1, H, V)[:S], last


def gated_delta_net(p, u, cfg, products_in, without=()):
    """``u`` [B, S, d] -> (the mixer's output, the mean square of each value
    head's last state [H], the mean decay)."""
    import jax
    import jax.numpy as jnp

    Bt, S, _ = u.shape
    Hk, H = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    K, V, eps = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["rms_norm_eps"]
    qkvz = _mm(u, p["in_proj_qkvz"]["kernel"], "bsd,de->bse", products_in)
    qkv, z = qkvz[..., :2 * Hk * K + H * V], qkvz[..., 2 * Hk * K + H * V:]
    ba = _mm(u, p["in_proj_ba"]["kernel"], "bsd,de->bse", products_in)
    b, a = ba[..., :H], ba[..., H:]
    w = p["conv_kernel"]                                            # [L, channels], no bias
    L = w.shape[0]
    padded = jnp.concatenate([jnp.zeros_like(qkv[:, :L - 1]), qkv], axis=1)
    qkv = jax.nn.silu(sum(w[j] * padded[:, j:j + S] for j in range(L)))
    q, k, v = qkv[..., :Hk * K], qkv[..., Hk * K:2 * Hk * K], qkv[..., 2 * Hk * K:]
    q, k, v = q.reshape(Bt, S, Hk, K), k.reshape(Bt, S, Hk, K), v.reshape(Bt, S, H, V)
    if "l2norm" not in without:
        q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q = q / K ** 0.5
    beta = jnp.ones_like(b) if "beta" in without else jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    if "decay" in without:
        g = jnp.zeros_like(g)
    os_, lasts = [], []
    for s in range(Bt):                                             # one sequence at a time
        o, last = recurrence(_rounded(q[s], products_in), _rounded(k[s], products_in), _rounded(v[s], products_in),
                             g[s], beta[s], cfg["gdn_chunk_size"] if "carry" in without else 0)
        os_.append(o)
        lasts.append(jnp.mean(last * last, axis=(1, 2)))
    o = jnp.stack(os_)
    y = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["out_norm"]
    if "z_gate" not in without:
        y = y * jax.nn.silu(z.reshape(Bt, S, H, V))
    return (_mm(y.reshape(Bt, S, H * V), p["out_proj"]["kernel"], "bsd,de->bse", products_in),
            jnp.mean(jnp.stack(lasts), axis=0), jnp.mean(jnp.exp(g)))


def rotary_first(x, theta, rotary_dim):
    """``x`` [S, H, D]: the first ``rotary_dim`` dimensions of each head
    turned, ``x cos + rotate_half(x) sin`` with ``rotate_half([a, b]) = [-b,
    a]`` over THOSE dimensions and the angle ``t * theta^(-2i/rotary_dim)``;
    the others as they are."""
    import jax.numpy as jnp

    S, R = x.shape[0], rotary_dim
    angle = jnp.arange(S)[:, None] / theta ** (jnp.arange(0, R, 2) / R)[None, :]           # [S, R/2]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], -1)[:, None, :]
    turned, kept = x[..., :R], x[..., R:]
    half = jnp.concatenate([-turned[..., R // 2:], turned[..., :R // 2]], -1)
    return jnp.concatenate([turned * cos + half * sin, kept], -1)


def gated_attention(p, u, cfg, products_in, without=()):
    """``u`` [B, S, d] -> (the attention layer's output, the mean of sigmoid(gate))."""
    import jax
    import jax.numpy as jnp

    B, S, _ = u.shape
    H, Hkv, D = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    R = D if "rope_whole" in without else int(D * cfg["partial_rotary_factor"])
    out, opened = [], []
    for b in range(B):                                              # one sequence at a time
        q_gate = _mm(u[b], p["q_proj"]["kernel"], "sd,de->se", products_in).reshape(S, H, 2 * D)
        q, gate = q_gate[..., :D], q_gate[..., D:]
        k = _mm(u[b], p["k_proj"]["kernel"], "sd,de->se", products_in).reshape(S, Hkv, D)
        v = _mm(u[b], p["v_proj"]["kernel"], "sd,de->se", products_in).reshape(S, Hkv, D)
        q, k = norm(q, p["q_norm"]["w"], eps, without), norm(k, p["k_norm"]["w"], eps, without)
        if "rope" not in without:
            q, k = rotary_first(q, theta, R), rotary_first(k, theta, R)
        k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))   # head h reads key/value head h // (H / Hkv)
        q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))        # [H, S, D]: a product per head
        rows = []
        for lo in range(0, S, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, S)
            rows.append(_compiled(attention_block, ("lo", "products_in"))(
                q[:, lo:hi], k[:, :hi], v[:, :hi], lo=lo, products_in=products_in))
        heads = jnp.concatenate(rows, axis=1).transpose(1, 0, 2)                   # [S, H, D]
        sig = jax.nn.sigmoid(gate)
        opened.append(jnp.mean(sig))
        out.append((heads if "attn_gate" in without else heads * sig).reshape(S, H * D))
    return _mm(jnp.stack(out), p["o_proj"]["kernel"], "bsd,de->bse", products_in), jnp.mean(jnp.stack(opened))


def router(p, bias, u, cfg):
    """``u`` [N, d] -> (probabilities [N, num_experts], the picks as a mask of
    k ones a row). ``bias`` is the program's buffer, which a softmax router
    does not read."""
    import jax
    import jax.numpy as jnp

    prob = jax.nn.softmax(jnp.einsum("nd,de->ne", u, p["router"]), axis=-1)   # the router is never rounded
    kth = jnp.sort(prob, axis=-1)[:, -cfg["num_experts_per_tok"]][:, None]
    return prob, (prob >= kth).astype(jnp.float32)


def weighted_expert(u, w, picked, w1, w2, w3, products_in):
    """``w_e E_e(u)`` [N, d] with ``w`` [N, 1], zero where the position did
    not pick the expert (``picked`` [N]). Where few positions picked it, the
    expert is computed over those rows alone (gathered into the smallest of
    ``ROUTED_SHARES`` of ``N`` that holds them, computed, added back to their
    positions); otherwise over every row times the mask, as the other plain
    models do. The same sum either way: 32 held experts over all 8,192
    positions of a sequence are 13 TFLOP of float32 products a step, and the
    run beside this process waits for it."""
    import jax
    import jax.numpy as jnp

    caps = [max(u.shape[0] // share, 1) for share in ROUTED_SHARES]

    def over_its_rows(cap):
        def fn(_):
            at = jnp.nonzero(picked, size=cap, fill_value=0)[0]
            there = (jnp.arange(cap) < picked.sum())[:, None]              # the fill beyond the rows present adds nothing
            rows = jnp.where(there, w[at] * swiglu(u[at], w1, w2, w3, products_in), 0.0)
            return jnp.zeros_like(u).at[at].add(rows)
        return fn

    over_every_row = lambda _: w * swiglu(u, w1, w2, w3, products_in)
    return jax.lax.switch(sum((picked.sum() > cap).astype(jnp.int32) for cap in caps),
                          [*map(over_its_rows, caps), over_every_row], None)


def gated_shared(u, gate, w1, w2, w3, products_in, gated=True):
    """``sigmoid(u . w_g) E_shared(u)``."""
    import jax
    import jax.numpy as jnp

    out = swiglu(u, w1, w2, w3, products_in)
    return out * jax.nn.sigmoid(jnp.einsum("nd,d->n", u, gate))[:, None] if gated else out


def experts_held(p, bias, u, cfg, products_in, chosen=None, without=()):
    """``u`` [N, d] -> (FF(u) over the experts held plus the gated shared
    expert, rows routed to each held expert, the router's own picks).
    ``chosen`` puts given picks in the place of the router's own."""
    import jax
    import jax.numpy as jnp

    held = cfg["experts_held"]
    mine = slice(held["offset"], held["offset"] + held["count"])
    prob, own = router(p, bias, u, cfg)
    chosen = own if chosen is None else chosen
    w = prob * chosen / (prob * chosen).sum(-1, keepdims=True)

    def add_expert(out, of):   # the loop over the held experts, its body compiled once
        w_e, chosen_e, w1, w2, w3 = of
        return out + _compiled(weighted_expert, ("products_in",))(
            u, w_e[:, None], chosen_e > 0, w1, w2, w3, products_in=products_in), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (w[:, mine].T, chosen[:, mine].T, p["w1"], p["w2"], p["w3"]))
    if "shared" not in without:
        out = out + _compiled(gated_shared, ("products_in", "gated"))(
            u, p["shared_gate"], p["shared_w1"], p["shared_w2"], p["shared_w3"], products_in=products_in,
            gated="shared_gate" not in without)
    return out, chosen[:, mine].sum(0), own


def is_attention(cfg, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def forward(variables, cfg, tokens, products_in=None, picks=None, without=()):
    """``tokens`` [B, S] -> (logits [B, S, V], stats as the program reports
    them: ``rms``, ``mixer_rms``, ``ff_rms`` per layer, ``gdn_state_ms`` (each
    value head's mean square), ``gdn_state_rms`` and ``gdn_decay_mean`` per
    Gated DeltaNet layer, ``attn_gate_mean`` per attention layer, ``rows`` per
    layer, and the router's own ``picks`` per layer). ``picks`` (layer index
    -> mask [B*S, num_experts]) routes by given picks instead."""
    import jax
    import jax.numpy as jnp

    params, eps = variables["params"], cfg["rms_norm_eps"]   # the buffers hold a selection bias a softmax router does not read
    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    x = params["embedding"][tokens]
    B, S, d = x.shape
    stats = {k: [] for k in ("rms", "mixer_rms", "ff_rms", "gdn_state_ms", "gdn_state_rms", "gdn_decay_mean",
                             "attn_gate_mean", "rows", "picks")}
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        u = norm(x, p["operator_norm"]["w"], eps, without)
        if is_attention(cfg, i):
            mixed, opened = jax.checkpoint(lambda m, u: gated_attention(m, u, cfg, products_in, without))(
                p["attention"], u)
            stats["attn_gate_mean"].append(opened)
        else:
            mixed, last_ms, decay = jax.checkpoint(lambda m, u: gated_delta_net(m, u, cfg, products_in, without))(
                p["gdn"], u)
            stats["gdn_state_ms"].append(last_ms)
            stats["gdn_state_rms"].append(state_rms(last_ms))
            stats["gdn_decay_mean"].append(decay)
        x = x + mixed
        u = norm(x, p["moe"]["norm"]["w"], eps, without).reshape(B * S, d)
        ff, rows, own = experts_held(p["moe"], None, u, cfg, products_in, None if picks is None else picks[i], without)
        stats["rows"].append(rows)
        stats["picks"].append(own)
        x = x + ff.reshape(B, S, d)
        stats["rms"].append(rms(x))
        stats["mixer_rms"].append(rms(mixed))
        stats["ff_rms"].append(rms(ff))
    h = norm(x, params["final_norm"]["w"], eps, without)
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
    keys = ("num_hidden_layers", "full_attention_interval", "linear_num_key_heads", "linear_key_head_dim",
            "linear_num_value_heads", "linear_value_head_dim", "gdn_chunk_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta", "rms_norm_eps",
            "num_experts_per_tok")
    cfg = {k: model_cfg[k] for k in keys}
    cfg["experts_held"] = {k: int(model_cfg["experts_held"][k]) for k in ("offset", "count")}
    return cfg


def named(total, stats, cfg) -> Dict[str, float]:
    """The loss and the stats under the names of the learner's log."""
    out = {"total_loss": float(total), "moe_overflow_rows": 0.0}  # the loop leaves no pick out
    layers = range(cfg["num_hidden_layers"])
    for i, (a, b, c, rows) in enumerate(zip(stats["rms"], stats["mixer_rms"], stats["ff_rms"], stats["rows"])):
        out[f"residual_rms/layer_{i}"] = float(a)
        out[f"mixer_rms/layer_{i}"] = float(b)
        out[f"ff_rms/layer_{i}"] = float(c)
        out.update({f"moe_rows/layer_{i}/expert_{e}": float(r) for e, r in enumerate(rows)})
        out[f"moe_rows_sum/layer_{i}"] = float(sum(float(r) for r in rows))
        out[f"moe_rows_max/layer_{i}"] = float(max(float(r) for r in rows))
    for name, there in (("gdn_state_rms", False), ("gdn_decay_mean", False), ("attn_gate_mean", True)):
        for i, s in zip([i for i in layers if is_attention(cfg, i) == there], stats[name]):
            out[f"{name}/layer_{i}"] = float(s)
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
    every held expert of every layer, the RMS of the residual stream, of the
    mixer's output and of the feed-forward output after every layer,
    ``state_rms`` of every Gated DeltaNet layer's last state and its mean
    decay, the mean gate of every attention layer, and up to
    ``GRADS_UP_TO_POSITIONS`` positions the gradient norm of every top-level
    module (``dyn/grad_norm/<module>``, the names of the step's dynamics tree).

    A sequence a call of one compiled function, so that the published widths
    at 8,192 positions fit beside a run's set-up; the sequences' losses, means
    and mean squares average, their rows add."""
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

        # the sequences side by side, a thread each: much of a sequence's pass (a recurrence of 8,192 dependent
        # steps a layer) keeps one core busy, and the run beside this process waits for it (PERF.md section 6, PR 36)
        with ThreadPoolExecutor(tokens.shape[0]) as sequences:
            per_seq = list(sequences.map(
                lambda b: jax.device_get(one(variables, tokens[b:b + 1], labels[b:b + 1])), range(tokens.shape[0])))
        over = lambda k: range(len(per_seq[0][1][k]))
        stats = {
            "rows": [sum(s["rows"][j] for _, s in per_seq) for j in over("rows")],
            **{k: [float(np.sqrt(np.mean([s[k][i] ** 2 for _, s in per_seq]))) for i in over(k)]
               for k in ("rms", "mixer_rms", "ff_rms")},
            **{k: [float(np.mean([s[k][i] for _, s in per_seq])) for i in over(k)]
               for k in ("gdn_decay_mean", "attn_gate_mean")},
            "gdn_state_rms": [float(state_rms(np.mean([s["gdn_state_ms"][i] for _, s in per_seq], axis=0)))
                              for i in over("gdn_state_ms")],
        }
        out = named(np.mean([total for total, _ in per_seq]), stats, cfg)
        out["forward_seconds"] = time.perf_counter() - t
        if tokens.size <= GRADS_UP_TO_POSITIONS:
            norms = jax.jit(lambda v: {module: jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
                                       for module, g in gradients(v, cfg, tokens, labels, products_in).items()})
            out.update({f"dyn/grad_norm/{module}": float(n) for module, n in norms(variables).items()})
    return out
