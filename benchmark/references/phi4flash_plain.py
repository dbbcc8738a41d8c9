"""``phi4flash`` (Phi-4-mini-flash-reasoning's language model) written out
plainly: forward pass, loss and gradients in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, from the published equations
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning, ``config.json``,
``model_type: phi4flash``, and the ``modeling_phi4flash.py`` beside it;
arXiv:2507.06607). It imports nothing of the program's model or operator code;
it reads the program's parameter tree (the same seed gives the same weights)
and its model config, and follows the same cut: the layers are the published
layers ``layers_held``, each under its published index, and the vocabulary is
the rows held.

  layer i          h = x + Mixer_i(LN(x)),  y = h + W_2 (silu(W_1 LN(h)) * W_3 LN(h));
                   LN(x) = (x - mean) / sqrt(var + eps) * scale + bias
  Mamba-1          (i even, i <= n/2)  [x, z] = W_in u; x <- silu(conv_4(x) + b);
                   [dt_r, B, C] = W_x x; dt = softplus(W_dt dt_r + dt_bias); A = -exp(A_log);
                   h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t^T over [inner, N], h_{-1} = 0,
                   AS WRITTEN: one ``lax.scan`` step a position, no chunks;
                   y_t = h_t C_t + D * x_t;  W_out (y * silu(z)). Layer n/2 stores M = y
  differential     (i odd, i <= n/2 + 1)  q, k, v = W u + b; query pair j = query heads (2j, 2j + 1)
  attention        reads key/value pair j // (H / Hkv) = key heads (k1, k2) and V = [v1, v2];
                   a1 = softmax(q1 k1^T / sqrt(D)) V, a2 = softmax(q2 k2^T / sqrt(D)) V, EACH
                   WRITTEN OUT, over the keys j of query i with (i - j >= 0), and for i < n/2
                   (i - j >= 0) & (i - j < sliding_window), the mask that comparison of positions;
                   lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
                   lambda_init = 0.8 - 0.6 exp(-0.3 i);
                   o_j = (1 - lambda_init) RMSNorm_2D(a1 - lambda a2) * g;  W_o [o_j] + b.
                   Layer n/2 + 1 stores its k and v
  memory unit      (i even, i >= n/2 + 2)  W_out (M * silu(W_in u))
  cross-attention  (i odd, i >= n/2 + 3)  q = W_q u + b; the differential form over the stored k, v
  output           LN, logits = h E^T over the rows of the embedding; mean next-token cross-entropy

What the config has no key for is the configuration's ``assumed``
(``benchmark/configs/phi4_mini_flash_v8_l6.json``). One thing is this file's
own and changes what is multiplied, not what comes out: a block of
``QUERY_BLOCK`` queries is not multiplied against all keys: a full or cross
layer's block takes the keys up to its own end (rounded up to a multiple of
``KEY_STEP``), a windowed layer's the keys from ``sliding_window`` rounded up
to whole blocks before its start, and the mask is the comparison above over
the positions of the slice. ``keys="all"`` multiplies against every key (a
tier-1 test holds the two to each other). How it is computed, not what: a
block is taken a pair and ``ROWS_AT_ONCE`` rows at a time, a layer's blocks
run ``BLOCKS_SIDE_BY_SIDE`` at a time and a batch's sequences side by side,
each on a thread of its own (a softmax over 335 MB of scores is passes that
one core makes alone: 100 s became 67 on 8 cores; my CPU run, PR 42). The recurrence is computed again
block by block in its backward pass (``jax.checkpoint`` over ``REMAT_BLOCK``
positions), which changes what is kept, not what is computed.

``products_in`` rounds both operands of every matrix product, and the
recurrence's ``x``, ``B`` and ``C``, to a narrower dtype first: how far a run
in that precision would part from this one (``float8_e4m3fn`` is the
precision below the configuration's bfloat16). ``without`` leaves one term of
the equations out or changes it (``OMISSIONS``): what a program with that
fault would report, to show that the cell's limits see it.
"""
from __future__ import annotations

import math
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Optional

# what is the same in every plain model is written once, in the first of them that has it: a piece compiled as one
# function, a product with its operands rounded, the SwiGLU; the rounding of one operand and the geometric mean over
# the channels of the RMS of each one's state (there: a head's)
from benchmark.references.lfm2_plain import _compiled, _mm, swiglu  # noqa: F401
from benchmark.references.nemotron_h_plain import _rounded, state_rms  # noqa: F401

QUERY_BLOCK = 512
KEY_STEP = 2048  # a full layer's block takes keys up to a multiple of this: 4 shapes to compile at 8,192 positions, not 16
REMAT_BLOCK = 256
ROWS_AT_ONCE = 256  # rows of a block whose scores are computed together
BLOCKS_SIDE_BY_SIDE = 8  # query blocks of a layer computed at once, each on a thread of its own
GRADS_UP_TO_POSITIONS = 4096  # gradient norms beside a set-up only at small sizes (the rehearsal): at the cell's they are the gradient tool's
OMISSIONS = ("window",        # the windowed layers see every key before the query
             "lambda",        # a1 alone: no second map is subtracted
             "pair_norm",     # a1 - lambda a2 as it is, no RMSNorm over the pair's 2 D
             "lambda_scale",  # the factor (1 - lambda_init) left out
             "layer_index",   # lambda_init from the layer's place in layers_held, not its published index
             "gated_memory",  # M taken AFTER the gate silu(z)
             "skip",          # y_t = h_t C_t without D x_t (in the memory too)
             "dt_bias",       # dt = softplus(W_dt dt_r)
             "carry")         # the state starts from zero every 256 positions


def layer_norm(x, p, eps):
    import jax.numpy as jnp

    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred / jnp.sqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * p["scale"] + p["bias"]


def layer_kind(cfg, published: int) -> str:
    """``mamba``, ``sliding``, ``full``, ``gmu`` or ``cross``, from the published index."""
    half = cfg["num_hidden_layers"] // 2
    if published % cfg["mb_per_layer"] == 0:
        return "mamba" if published <= half else "gmu"
    return "sliding" if published < half else "full" if published == half + 1 else "cross"


def recurrence(x, dt, A, B, C, reset_every: int = 0):
    """The selective scan of one sequence, a step a position. ``x``, ``dt`` [S, c], ``A`` [c, N], ``B``/``C``
    [S, N] -> (``y`` [S, c] without the skip, the last state [c, N]). ``reset_every``: the fault of a chunked
    scan that drops its carry."""
    import jax
    import jax.numpy as jnp

    S, c = x.shape

    def step(h, at):
        x_t, dt_t, b_t, c_t, t = at
        if reset_every:
            h = jnp.where(t % reset_every == 0, 0.0, h)
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=-1)

    @jax.checkpoint
    def block(h, ats):
        return jax.lax.scan(step, h, ats)

    size = min(REMAT_BLOCK, S)
    pad = -S % size                                                 # dt = 0: the state stays, the rows are cut
    ats = tuple(jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)).reshape(-1, size, *t.shape[1:])
                for t in (x, dt, B, C, jnp.arange(S)))
    last, y = jax.lax.scan(block, jnp.zeros(A.shape, x.dtype), ats)
    return y.reshape(-1, c)[:S], last


def mamba(p, u, N, R, products_in, without):
    """``u`` [B, S, d] -> (the mixer's output, the pre-gate ``y`` [B, S, inner], the mean square of each channel's
    last state [inner])."""
    import jax
    import jax.numpy as jnp

    Bt, S, _ = u.shape
    x, z = jnp.split(_mm(u, p["in_proj"]["kernel"], "bsd,de->bse", products_in), 2, axis=-1)
    w = p["conv_kernel"]                                            # [L, inner]
    L = w.shape[0]
    padded = jnp.concatenate([jnp.zeros_like(x[:, :L - 1]), x], axis=1)
    x = jax.nn.silu(sum(w[k] * padded[:, k:k + S] for k in range(L)) + p.get("conv_bias", 0.0))
    dt, B, C = jnp.split(_mm(x, p["x_proj"]["kernel"], "bse,ef->bsf", products_in), [R, R + N], axis=-1)
    dt = _mm(dt, p["dt_proj"]["kernel"], "bsr,re->bse", products_in)
    dt = jax.nn.softplus(dt if "dt_bias" in without else dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    ys, lasts = [], []
    for b in range(Bt):                                             # one sequence at a time
        y, last = recurrence(_rounded(x[b], products_in), dt[b], A, _rounded(B[b], products_in),
                             _rounded(C[b], products_in), REMAT_BLOCK if "carry" in without else 0)
        ys.append(y if "skip" in without else y + p["D"] * x[b])
        lasts.append(jnp.mean(last * last, axis=-1))
    y = jnp.stack(ys)
    gated = y * jax.nn.silu(z)
    return (_mm(gated, p["out_proj"]["kernel"], "bse,ed->bsd", products_in),
            gated if "gated_memory" in without else y, jnp.mean(jnp.stack(lasts), axis=0))


def gmu(p, u, memory, products_in):
    import jax

    gate = jax.nn.silu(_mm(u, p["in_proj"]["kernel"], "bsd,de->bse", products_in))
    return _mm(memory * gate, p["out_proj"]["kernel"], "bse,ed->bsd", products_in)


def one_map(q, k, V, at, first, window, products_in):
    """``softmax(q k^T / sqrt(D)) V`` of every pair: ``q`` [pairs, rows, D] at the positions ``at``, ``k`` [pairs,
    keys, D] and ``V`` [pairs, keys, 2 D] at the positions ``first``.. ; the mask is the comparison of positions.
    A pair and ``ROWS_AT_ONCE`` rows at a time, so that a group of rows' scores stay in a core's cache."""
    import jax
    import jax.numpy as jnp

    pairs, n, D = q.shape
    group = ROWS_AT_ONCE if n % ROWS_AT_ONCE == 0 else n

    def rows(qr, at_r, kp, Vp):
        score = _mm(qr, kp, "qd,kd->qk", products_in) / D ** 0.5
        apart = at_r[:, None] - (first + jnp.arange(kp.shape[0]))[None, :]        # i - j
        seen = apart >= 0 if window is None else (apart >= 0) & (apart < window)
        return _mm(jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1), Vp, "qk,kd->qd", products_in)

    def of_pair(of):
        qp, kp, Vp = of
        return jax.lax.map(lambda g: rows(g[0], g[1], kp, Vp), (qp.reshape(-1, group, D), at.reshape(-1, group))).reshape(n, -1)

    return jax.lax.map(of_pair, (q, k, V))


def pair_block(q1, q2, k1, k2, V, lo, first, window, products_in):
    """A block of queries ``lo``.. : the two maps of every pair, each written out."""
    import jax.numpy as jnp

    at = lo + jnp.arange(q1.shape[1])
    return (one_map(q1, k1, V, at, first, window, products_in), one_map(q2, k2, V, at, first, window, products_in))


def spans(S: int, window: Optional[int], keys: str):
    """(lo, hi, first key, key end) of every query block."""
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        if keys == "all":
            out.append((lo, hi, 0, S))
        elif window is None:
            out.append((lo, hi, 0, min(S, -(-hi // KEY_STEP) * KEY_STEP)))
        else:
            out.append((lo, hi, max(0, lo - -(-window // QUERY_BLOCK) * QUERY_BLOCK), hi))
    return out


def _side_by_side(one, items, traced: bool):
    """``[one(item) for item in items]``, ``BLOCKS_SIDE_BY_SIDE`` at a time on threads of their own: a block's
    softmax is passes over 335 MB of scores that one core makes alone. Under a trace (``gradients``) in order."""
    import jax

    if BLOCKS_SIDE_BY_SIDE < 2 or traced:
        return [one(item) for item in items]
    with ThreadPoolExecutor(BLOCKS_SIDE_BY_SIDE) as pool:
        return list(pool.map(one, items))


def differential(p, u, kv, H, Hkv, lambda_init, window, eps, products_in, without=(), keys: str = "band"):
    """``u`` [B, S, d] -> (the layer's output, (k, v) [B, S, Hkv, D] as projected or handed in, lambda)."""
    import jax
    import jax.numpy as jnp

    Bt, S, d = u.shape
    D = d // H
    proj = lambda name: _mm(u, p[name]["kernel"], "bsd,de->bse", products_in) + p[name]["bias"]
    q = proj("q_proj").reshape(Bt, S, H, D)
    if kv is None:
        kv = (proj("k_proj").reshape(Bt, S, Hkv, D), proj("v_proj").reshape(Bt, S, Hkv, D))
    k, v = kv
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
           + lambda_init)
    pairs, per = H // 2, (H // 2) // (Hkv // 2)                  # query pairs; query pairs a key/value pair
    out = []
    for b in range(Bt):                                             # one sequence at a time
        of_pair = lambda t, r: jnp.repeat(t[b][:, r::2], per, axis=1).transpose(1, 0, 2)   # key head 2 (j // per) + r
        q1, q2 = q[b][:, 0::2].transpose(1, 0, 2), q[b][:, 1::2].transpose(1, 0, 2)        # [pairs, S, D]
        k1, k2 = of_pair(k, 0), of_pair(k, 1)
        V = jnp.concatenate([of_pair(v, 0), of_pair(v, 1)], axis=-1)                       # [pairs, S, 2 D]
        block = lambda span: _compiled(pair_block, ("lo", "first", "window", "products_in"))(
            q1[:, span[0]:span[1]], q2[:, span[0]:span[1]], k1[:, span[2]:span[3]], k2[:, span[2]:span[3]],
            V[:, span[2]:span[3]], lo=span[0], first=span[2], window=window, products_in=products_in)
        a1, a2 = (jnp.concatenate(maps, axis=1) for maps in zip(*_side_by_side(block, spans(S, window, keys), isinstance(u, jax.core.Tracer))))
        o = a1 if "lambda" in without else a1 - lam * a2
        if "pair_norm" not in without:
            o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["pair_norm"]["scale"]
        if "lambda_scale" not in without:
            o = o * (1.0 - lambda_init)
        out.append(o.transpose(1, 0, 2).reshape(S, pairs * 2 * D))
    return _mm(jnp.stack(out), p["o_proj"]["kernel"], "bse,ed->bsd", products_in) + p["o_proj"]["bias"], kv, lam


def head_and_loss(x, norm, embedding, labels, eps, products_in):
    import jax
    import jax.numpy as jnp

    logits = _mm(layer_norm(x, norm, eps), embedding, "bsd,vd->bsv", products_in)
    log_p = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(log_p, labels[..., None], axis=-1)), logits


def layer(p, x, handed, cfg, i: int, products_in=None, without=(), keys: str = "band"):
    """Layer ``i`` of ``layers_held``: (x, what earlier layers stored) -> (x, what is stored now, its statistics)."""
    import jax.numpy as jnp

    eps, published = cfg["layer_norm_eps"], cfg["layers_held"][i]
    half, kind = cfg["num_hidden_layers"] // 2, layer_kind(cfg, published)
    B, S, d = x.shape
    rms = lambda t: jnp.sqrt(jnp.mean(t * t))
    u, stats = layer_norm(x, p["operator_norm"], eps), {}
    if kind == "mamba":
        mixed, memory, last_ms = _compiled(mamba, ("N", "R", "products_in", "without"))(
            p["mamba"], u, N=cfg["mamba_d_state"], R=cfg["mamba_dt_rank"], products_in=products_in,
            without=tuple(without))
        stats["ssm_state_ms"] = last_ms
        if published == half:
            handed, stats["memory_ms"] = dict(handed, memory=memory), jnp.mean(memory * memory)
    elif kind == "gmu":
        mixed = _compiled(gmu, ("products_in",))(p["gmu"], u, handed["memory"], products_in=products_in)
    else:
        index = i if "layer_index" in without else published
        window = cfg["sliding_window"] if kind == "sliding" and "window" not in without else None
        mixed, kv, stats["diff_lambda"] = differential(
            p["attn"], u, handed["kv"] if kind == "cross" else None, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            0.8 - 0.6 * math.exp(-0.3 * index), window, eps, products_in, without, keys)
        if published == half + 1:
            handed = dict(handed, kv=kv)
    x = x + mixed
    m = p["dense_mlp"]
    ff = _compiled(swiglu, ("products_in",))(
        layer_norm(x, p["ffn_norm"], eps).reshape(B * S, d), m["w1"]["kernel"], m["w2"]["kernel"], m["w3"]["kernel"],
        products_in=products_in).reshape(B, S, d)
    x = x + ff
    return x, handed, dict(stats, rms=rms(x), mixer_rms=rms(mixed), ff_rms=rms(ff))


def forward(variables, cfg, tokens, products_in=None, without=(), keys: str = "band", labels=None):
    """``tokens`` [B, S] -> (logits [B, S, V], stats: ``rms``, ``mixer_rms`` and ``ff_rms`` a layer as lists,
    ``ssm_state_ms`` (each channel's mean square), ``diff_lambda`` and ``memory_ms`` by layer index as dicts,
    ``picks`` as the siblings report their routers' and empty here; with ``labels`` the loss is ``stats["loss"]``)."""
    import jax.numpy as jnp

    params = variables["params"]
    x, handed = params["embedding"][tokens], {}
    stats = {"rms": [], "mixer_rms": [], "ff_rms": [], "ssm_state_ms": {}, "diff_lambda": {}, "memory_ms": {},
             "picks": []}                                           # the routers' own picks: the model has none
    for i in range(len(cfg["layers_held"])):
        x, handed, of_layer = layer(params[f"layer_{i}"], x, handed, cfg, i, products_in, without, keys)
        for k, value in of_layer.items():
            if isinstance(stats[k], dict):
                stats[k][i] = value
            else:
                stats[k].append(value)
    total, logits = _compiled(head_and_loss, ("eps", "products_in"))(
        x, params["final_norm"], params["embedding"], jnp.zeros(tokens.shape, jnp.int32) if labels is None else labels,
        eps=cfg["layer_norm_eps"], products_in=products_in)
    return logits, dict(stats, loss=total)


def loss(params, variables, cfg, tokens, labels, products_in=None, without=(), keys: str = "band"):
    """Mean next-token cross-entropy over every position, and the stats."""
    logits, stats = forward({**variables, "params": params}, cfg, tokens, products_in, without, keys, labels)
    return stats.pop("loss"), (logits, stats)


def plain_config(model_cfg) -> Dict:
    """The program's model config as plain Python values."""
    keys = ("num_hidden_layers", "mb_per_layer", "num_attention_heads", "num_key_value_heads", "sliding_window",
            "mamba_d_state", "mamba_dt_rank", "layer_norm_eps")
    return dict({k: model_cfg[k] for k in keys}, layers_held=[int(i) for i in model_cfg["layers_held"]])


def named(total, stats, cfg=None) -> Dict[str, float]:
    """The loss and the stats of ``loss`` (or of ``first_step``'s mean over the sequences) under the names of the
    learner's log."""
    import numpy as np

    out = {"total_loss": float(total), "moe_overflow_rows": 0.0}   # the model has no experts
    for i, (a, b, c) in enumerate(zip(stats["rms"], stats["mixer_rms"], stats["ff_rms"])):
        out[f"residual_rms/layer_{i}"] = float(a)
        out[f"mixer_rms/layer_{i}"] = float(b)
        out[f"ff_rms/layer_{i}"] = float(c)
    out.update({f"ssm_state_rms/layer_{i}": float(state_rms(ms)) for i, ms in stats["ssm_state_ms"].items()})
    out.update({f"diff_lambda/layer_{i}": float(s) for i, s in stats["diff_lambda"].items()})
    out.update({"memory_rms": float(np.sqrt(ms)) for ms in stats["memory_ms"].values()})
    return out


def gradients(variables, cfg, tokens, labels, products_in: Optional[str] = None, picks=None):
    """The gradient of the batch's loss by every parameter, one sequence at a time (the batch's loss is the mean
    of its sequences' losses). The pieces compiled as one function each (``_compiled``) are computed again in
    their backward pass. ``picks`` is the gradient tool's for models with a router; this one has none."""
    import jax

    total = None
    for b in range(tokens.shape[0]):
        g = jax.grad(lambda p: loss(p, variables, cfg, tokens[b:b + 1], labels[b:b + 1], products_in)[0])(
            variables["params"])
        total = g if total is None else jax.tree.map(lambda x, y: x + y, total, g)
    return jax.tree.map(lambda x: x / tokens.shape[0], total)


def first_step(learner, batch, products_in: Optional[str] = None, without=()) -> Dict[str, float]:
    """The untrained weights on one batch: ``total_loss``, the RMS of the residual stream, of the mixer's output
    and of the feed-forward's after every layer, ``state_rms`` of every Mamba-1 layer's last state, every
    attention layer's ``lambda``, the RMS of the memory, and up to ``GRADS_UP_TO_POSITIONS`` positions the
    gradient norm of every top-level module (``dyn/grad_norm/<module>``, the names of the step's dynamics tree).

    A sequence a call, so that the published widths at 8,192 positions fit beside a run's set-up; the sequences'
    losses and mean squares average."""
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
        def one(b):
            with jax.default_matmul_precision("highest"):                   # a thread's own setting
                total, (_, stats) = loss(variables["params"], variables, cfg, tokens[b:b + 1], labels[b:b + 1],
                                         products_in, without)
                return jax.device_get((total, stats))

        # the sequences side by side: one's recurrence, a core's work, runs beside the other's products
        with ThreadPoolExecutor(tokens.shape[0]) as pool:
            per_seq = list(pool.map(one, range(tokens.shape[0])))
        over = lambda k: per_seq[0][1][k]
        stats = {
            **{k: [float(np.sqrt(np.mean([s[k][i] ** 2 for _, s in per_seq]))) for i in range(len(over(k)))]
               for k in ("rms", "mixer_rms", "ff_rms")},
            **{k: {i: np.mean([s[k][i] for _, s in per_seq], axis=0) for i in over(k)} for k in ("ssm_state_ms", "memory_ms")},
            "diff_lambda": over("diff_lambda"),
        }
        out = named(np.mean([total for total, _ in per_seq]), stats)
        out["forward_seconds"] = time.perf_counter() - t
        if tokens.size <= GRADS_UP_TO_POSITIONS:
            # compiled as one function: at these sizes the pieces' dispatch is the cost
            grads = jax.jit(lambda v: gradients(v, cfg, tokens, labels, products_in))(variables)
            out.update({f"dyn/grad_norm/{module}": float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
                        for module, g in grads.items()})
    return out
