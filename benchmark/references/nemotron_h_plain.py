"""``nemotron_h`` written out plainly: forward pass, loss and gradients in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, from
the published equations
(https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16,
``config.json``, ``model_type: nemotron_h``). It imports nothing of the
program's model or operator code; it reads the program's parameter tree (the
same seed gives the same weights) and its model config, and follows the same
cut: the router scores all ``n_routed_experts``, a position's weights are
normalised over all ``num_experts_per_tok`` picks, of the picks only the
experts in ``experts_held`` add to the result, and the shared expert is whole.

  layer            x <- x + Mixer_i(RMSNorm(x)), Mixer_i by the pattern's letter
  M (Mamba-2)      [z, xBC, dt] = W_in u; xBC <- silu(conv_4(xBC) + b);
                   x [H, P], B [G, N], C [G, N] from xBC (H/G heads share a
                   group's B, C); dt <- softplus(dt + dt_bias); A = -exp(A_log);
                   h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t + D x_t
                   AS WRITTEN: one ``lax.scan`` step a position, no chunks;
                   y <- RMSNorm over groups of H P / G (y * silu(z)) * g; W_out y
  * (attention)    causal softmax(q k^T / sqrt(D)) v, a key/value head for every
                   H / Hkv query heads, no rotary embedding, no q/k norm; by
                   query blocks, keys up to the block
  E (experts)      s = sigmoid(W_r u); sel = top_k(s + b);
                   w_e = scale * s_e / (sum_sel s + 1e-6);
                   sum over e in sel that is held of w_e W2_e relu(W1_e u)^2 (a
                   loop over the held experts, each over all positions, times a
                   mask) + W2_s relu(W1_s u)^2
  output           RMSNorm, logits = h W_head; mean next-token cross-entropy

Departures from the published model are the configuration's (``assumed`` in
``benchmark/configs/nemotron_twotower_30b_a3b_ep16_l9.json``): no position
encoding in attention (``rope_theta`` is unused), a fixed ``expert_bias``, no
auxiliary loss, and only the tower ``config.json`` defines (no denoiser tower,
no diffusion objective). The recurrence is computed again block by block in
its backward pass (``jax.checkpoint`` over ``REMAT_BLOCK`` positions), which
changes what is kept, not what is computed.

``products_in`` rounds both operands of every matrix product, and the
recurrence's ``x``, ``B`` and ``C``, to a narrower dtype first: how far a run
in that precision would part from this one (``float8_e4m3fn`` is the
precision below the configuration's bfloat16). ``without`` leaves one term of
the equations out (``OMISSIONS``): what a program with that fault would
report, to show that the cell's limits see it.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

# what is the same in every plain model is written once, in the first of them: a piece compiled as one
# function, RMSNorm, a block of queries against the keys before it, the sigmoid router with its bias
from benchmark.references.lfm2_plain import _compiled, attention_block, rms_norm, router  # noqa: F401

QUERY_BLOCK = 512
REMAT_BLOCK = 256
GRADS_UP_TO_POSITIONS = 4096  # gradients cost three forwards: beside a set-up only at small sizes
OMISSIONS = ("skip",      # y_t = h_t C_t without D x_t
             "dt_bias",   # dt = softplus(dt)
             "carry",     # the state starts from zero every chunk_size positions
             "shared",    # no shared expert
             "square",    # relu(.) in place of relu(.)^2
             "scale")     # routed_scaling_factor left out


def _rounded(t, products_in):
    import jax
    import jax.numpy as jnp

    if products_in is None:
        return t
    # rounded on the way in; a gradient passes the rounding as it came
    return t + jax.lax.stop_gradient(t.astype(products_in).astype(jnp.float32) - t)


def _mm(a, b, spec: str, products_in):
    import jax.numpy as jnp

    return jnp.einsum(spec, _rounded(a, products_in), _rounded(b, products_in))


def recurrence(x, dt, A, B, C, reset_every: int = 0):
    """The state-space recurrence of one sequence, a step a position.
    ``x`` [S, H, P], ``dt`` [S, H], ``A`` [H], ``B``/``C`` [S, G, N] ->
    (``y`` [S, H, P] without the skip, the last state [H, P, N]).
    ``reset_every``: the fault of a chunked scan that drops its carry."""
    import jax
    import jax.numpy as jnp

    S, H, P = x.shape
    G, N = B.shape[1:]
    per_head = lambda t: jnp.repeat(t, H // G, axis=0)             # [G, N] -> [H, N]: head h reads group h // (H / G)

    def step(h, at):
        x_t, dt_t, b_t, c_t, t = at
        if reset_every:
            h = jnp.where(t % reset_every == 0, 0.0, h)
        h = jnp.exp(dt_t * A)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * per_head(b_t)[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, per_head(c_t))

    @jax.checkpoint
    def block(h, ats):
        return jax.lax.scan(step, h, ats)

    size = min(REMAT_BLOCK, S)
    pad = -S % size                                                 # dt = 0: the state stays, the rows are cut
    ats = tuple(jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(-1, size, *t.shape[1:])
                for t in (x, dt, B, C, jnp.arange(S)))
    last, y = jax.lax.scan(block, jnp.zeros((H, P, N), x.dtype), ats)
    return y.reshape(-1, H, P)[:S], last


def mamba(p, u, H, P, G, N, chunk, eps, products_in, without):
    """``u`` [B, S, d] -> (the mixer's output, the mean square of each head's last state [H])."""
    import jax
    import jax.numpy as jnp

    Bt, S, _ = u.shape
    inner, bc = H * P, G * N
    z, xbc, dt = jnp.split(_mm(u, p["in_proj"]["kernel"], "bsd,de->bse", products_in),
                           [inner, 2 * inner + 2 * bc], axis=-1)
    w = p["conv_kernel"]                                            # [L, channels]
    L = w.shape[0]
    padded = jnp.concatenate([jnp.zeros_like(xbc[:, :L - 1]), xbc], axis=1)
    xbc = sum(w[k] * padded[:, k:k + S] for k in range(L)) + p.get("conv_bias", 0.0)
    x, B, C = jnp.split(jax.nn.silu(xbc), [inner, inner + bc], axis=-1)
    dt = jax.nn.softplus(dt if "dt_bias" in without else dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    ys, lasts = [], []
    for b in range(Bt):                                             # one sequence at a time
        y, last = recurrence(_rounded(x[b].reshape(S, H, P), products_in), dt[b], A,
                             _rounded(B[b].reshape(S, G, N), products_in),
                             _rounded(C[b].reshape(S, G, N), products_in),
                             chunk if "carry" in without else 0)
        if "skip" not in without:
            y = y + p["D"][:, None] * x[b].reshape(S, H, P)
        ys.append(y.reshape(S, inner))
        lasts.append(jnp.mean(last * last, axis=(1, 2)))
    t = jnp.stack(ys) * jax.nn.silu(z)
    g = t.reshape(Bt, S, G, inner // G)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    y = g.reshape(Bt, S, inner) * p["gated_norm"]["scale"]
    return _mm(y, p["out_proj"]["kernel"], "bsd,de->bse", products_in), jnp.mean(jnp.stack(lasts), axis=0)


def state_rms(per_head):
    """The geometric mean over the heads of the RMS of each head's state, from their mean squares."""
    import jax.numpy as jnp

    return jnp.exp(0.5 * jnp.mean(jnp.log(per_head + 1e-30)))


def attention(p, u, H, Hkv, D, products_in):
    """``H`` query heads over ``Hkv`` key/value heads of size ``D``, no positions."""
    import jax.numpy as jnp

    B, S, _ = u.shape
    out = []
    for b in range(B):                                              # one sequence at a time
        q = _mm(u[b], p["q_proj"]["kernel"], "sd,de->se", products_in).reshape(S, H, D)
        k = _mm(u[b], p["k_proj"]["kernel"], "sd,de->se", products_in).reshape(S, Hkv, D)
        v = _mm(u[b], p["v_proj"]["kernel"], "sd,de->se", products_in).reshape(S, Hkv, D)
        k, v = (jnp.repeat(t, H // Hkv, axis=1) for t in (k, v))   # head h reads key/value head h // (H / Hkv)
        q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))        # [H, S, D]: a product per head
        rows = []
        for lo in range(0, S, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, S)
            rows.append(_compiled(attention_block, ("lo", "products_in"))(
                q[:, lo:hi], k[:, :hi], v[:, :hi], lo=lo, products_in=products_in))
        out.append(jnp.concatenate(rows, axis=1).transpose(1, 0, 2).reshape(S, H * D))
    return _mm(jnp.stack(out), p["o_proj"]["kernel"], "bsd,de->bse", products_in)


def relu2_expert(u, w1, w2, products_in, square=True):
    """``W_2 relu(W_1 u)^2``."""
    import jax

    hidden = jax.nn.relu(_mm(u, w1, "nd,df->nf", products_in))
    return _mm(hidden * hidden if square else hidden, w2, "nf,fd->nd", products_in)


def weighted_expert(u, w, w1, w2, products_in, square=True):
    """``w_e E_e(u)`` with ``w`` [N, 1]: zero where the position did not pick the expert."""
    return w * relu2_expert(u, w1, w2, products_in, square)


def experts_held(p, bias, u, cfg, products_in, chosen=None, without=()):
    """``u`` [N, d] -> (FF(u) over the experts held plus the shared expert,
    rows routed to each held expert, the router's own picks). ``chosen`` puts
    given picks in the place of the router's own."""
    import jax.numpy as jnp

    held = cfg["experts_held"]
    s, own = router(p, bias, u, cfg)
    chosen = own if chosen is None else chosen
    scale = 1.0 if "scale" in without else cfg["routed_scaling_factor"]
    w = scale * s * chosen / ((s * chosen).sum(-1, keepdims=True) + 1e-6)
    square = "square" not in without
    out = jnp.zeros_like(u)
    for j in range(held["count"]):
        e = held["offset"] + j
        out = out + _compiled(weighted_expert, ("products_in", "square"))(
            u, w[:, e:e + 1], p["w1"][j], p["w2"][j], products_in=products_in, square=square)
    if "shared_w1" in p and "shared" not in without:
        out = out + _compiled(relu2_expert, ("products_in", "square"))(
            u, p["shared_w1"], p["shared_w2"], products_in=products_in, square=square)
    return out, chosen[:, held["offset"]:held["offset"] + held["count"]].sum(0), own


def forward(variables, cfg, tokens, products_in=None, picks=None, without=()):
    """``tokens`` [B, S] -> (logits [B, S, V], stats as the program reports
    them: ``rms``, ``mixer_rms`` per layer, ``ssm_state_rms`` per ``M`` layer
    (``state_rms`` of ``ssm_state_ms``, each head's mean square),
    ``rows`` per ``E`` layer, and the router's own ``picks`` per ``E`` layer).
    ``picks`` (layer index -> mask [B*S, n_routed_experts]) routes by given
    picks instead."""
    import jax.numpy as jnp

    params, buffers = variables["params"], variables.get("buffers", {})
    eps = cfg["norm_eps"]
    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    x = params["embedding"][tokens]
    B, S, d = x.shape
    stats = {"rms": [], "mixer_rms": [], "ssm_state_ms": [], "ssm_state_rms": [], "rows": [], "picks": []}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = params[f"layer_{i}"]
        if kind == "M":
            u = rms_norm(x, p["operator_norm"]["scale"], eps)
            out, last_ms = _compiled(mamba, ("H", "P", "G", "N", "chunk", "eps", "products_in", "without"))(
                p["mamba"], u, H=cfg["mamba_num_heads"], P=cfg["mamba_head_dim"], G=cfg["n_groups"],
                N=cfg["ssm_state_size"], chunk=cfg["chunk_size"], eps=eps, products_in=products_in,
                without=tuple(without))
            stats["ssm_state_ms"].append(last_ms)
            stats["ssm_state_rms"].append(state_rms(last_ms))
        elif kind == "*":
            u = rms_norm(x, p["operator_norm"]["scale"], eps)
            out = attention(p["attention"], u, cfg["num_attention_heads"], cfg["num_key_value_heads"],
                            cfg["head_dim"], products_in)
        else:
            u = rms_norm(x, p["moe"]["norm"]["scale"], eps).reshape(B * S, d)
            out, rows, own = experts_held(p["moe"], buffers[f"layer_{i}"]["moe"]["expert_bias"], u, cfg,
                                          products_in, None if picks is None else picks[i], without)
            out = out.reshape(B, S, d)
            stats["rows"].append(rows)
            stats["picks"].append(own)
        x = x + out
        stats["rms"].append(rms(x))
        stats["mixer_rms"].append(rms(out))
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
    keys = ("hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
            "chunk_size", "num_attention_heads", "num_key_value_heads", "head_dim", "norm_eps",
            "num_experts_per_tok", "routed_scaling_factor", "use_expert_bias")
    cfg = {k: model_cfg[k] for k in keys}
    cfg["experts_held"] = {k: int(model_cfg["experts_held"][k]) for k in ("offset", "count")}
    return cfg


def named(total, stats, cfg) -> Dict[str, float]:
    """The loss and the stats under the names of the learner's log."""
    out = {"total_loss": float(total), "moe_overflow_rows": 0.0}  # the loop leaves no pick out
    pattern = cfg["hybrid_override_pattern"]
    for i, (a, b) in enumerate(zip(stats["rms"], stats["mixer_rms"])):
        out[f"residual_rms/layer_{i}"] = float(a)
        out[f"mixer_rms/layer_{i}"] = float(b)
    for i, s in zip([i for i, k in enumerate(pattern) if k == "M"], stats["ssm_state_rms"]):
        out[f"ssm_state_rms/layer_{i}"] = float(s)
    for i, rows in zip([i for i, k in enumerate(pattern) if k == "E"], stats["rows"]):
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
    every held expert of every ``E`` layer, the RMS of the residual stream and
    of the mixer's output after every layer, ``state_rms`` of every ``M``
    layer's last state, and up to ``GRADS_UP_TO_POSITIONS`` positions the gradient
    norm of every top-level module (``dyn/grad_norm/<module>``, the names of
    the step's dynamics tree).

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
                   for i in range(len(per_seq[0][1][k]))] for k in ("rms", "mixer_rms")},
            "ssm_state_rms": [float(state_rms(np.mean([s["ssm_state_ms"][i] for _, s in per_seq], axis=0)))
                              for i in range(len(per_seq[0][1]["ssm_state_ms"]))],
        }
        out = named(np.mean([total for total, _ in per_seq]), stats, cfg)
        out["forward_seconds"] = time.perf_counter() - t
        if tokens.size <= GRADS_UP_TO_POSITIONS:
            for module, g in gradients(variables, cfg, tokens, labels, products_in).items():
                out[f"dyn/grad_norm/{module}"] = float(
                    jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
    return out
