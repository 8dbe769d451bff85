"""Yardstick of the dense decoder (Mistral-style): its sizes, its plain
float32 reference, the program's weights under the reference's names,
and the operations its work needs.  The contract is in ``cells.py``.

Imports nothing of the program and takes nothing it made.  Weights and
training rows are made here from the seed, by the scheme the model's
initialiser and data pipeline publish (same key splits, same draws), and
the mathematics follows the published model: RMSNorm in the (1 + scale)
form, rotary embeddings on halves, grouped-query attention with a causal
sliding window, SwiGLU, an untied head, mean token cross-entropy, and
AdamW with global-norm clipping and linear warm-up.

Every matrix product runs at ``precision="highest"``.  ``quant`` names a
lower precision (``"float8_e4m3fn"``, ``"bfloat16"``) into which the
operands of every product are rounded: that is the control, the step a
later change would be tempted by, which the comparison has to fail.

Parameters are held as the configuration stores them (bfloat16) and
upcast for the arithmetic; moments are float32.  Attention runs over
blocks of queries so that one 4096-token sequence never holds all its
scores at once.

Model operations count what the forward (and, for training, backward)
pass requires: two operations per multiply-add of every weight matrix
and of every attended (query, key) pair; recomputation under remat is
not counted, and neither are the masked-out scores the program computes.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
STORE = jnp.bfloat16
Q_BLOCK = 512


def dims(config: Dict) -> Dict:
    """The sizes the yardstick computes with, read from the published
    keys of a configuration file."""
    heads = config["num_attention_heads"]
    return {
        "layers": config["num_hidden_layers"],
        "d_model": config["hidden_size"],
        "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim",
                               config["hidden_size"] // heads),
        "d_ff": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "window": config.get("sliding_window") or 0,
        "rope_theta": config["rope_theta"],
        "norm_eps": config["rms_norm_eps"],
        "tie": config.get("tie_word_embeddings", False),
    }


# ---------------------------------------------------------------------------
# weights and rows from the seed
# ---------------------------------------------------------------------------

def _normal(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(STORE)


def layer_keys(dims: Dict, seed: int):
    """The per-layer keys: a 4-way split of the seed's key, its second
    entry split once (one layer kind) and then over the layers."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    (pkey,) = jax.random.split(keys[1], 1)
    return keys, jax.random.split(pkey, dims["layers"])


def init_layer(dims: Dict, key) -> Dict:
    d, h, kv, hd, ff = (dims["d_model"], dims["heads"], dims["kv_heads"],
                        dims["head_dim"], dims["d_ff"])
    ka, km, _, _ = jax.random.split(key, 4)
    ks = jax.random.split(ka, 6)
    kf = jax.random.split(km, 3)
    return {
        "ln1": jnp.zeros((d,), STORE), "ln2": jnp.zeros((d,), STORE),
        "wq": _normal(ks[0], (d, h * hd), 1 / math.sqrt(d)),
        "wk": _normal(ks[1], (d, kv * hd), 1 / math.sqrt(d)),
        "wv": _normal(ks[2], (d, kv * hd), 1 / math.sqrt(d)),
        "wo": _normal(ks[3], (h * hd, d), 1 / math.sqrt(h * hd)),
        "gate": _normal(kf[0], (d, ff), 1 / math.sqrt(d)),
        "up": _normal(kf[1], (d, ff), 1 / math.sqrt(d)),
        "down": _normal(kf[2], (ff, d), 1 / math.sqrt(ff)),
    }


def init_outer(dims: Dict, seed: int) -> Dict:
    keys, _ = layer_keys(dims, seed)
    d, v = dims["d_model"], dims["vocab"]
    return {"embed": _normal(keys[0], (v, d), 0.02),
            "final_norm": jnp.zeros((d,), STORE),
            "head": _normal(keys[3], (d, v), 1 / math.sqrt(d))}


def init_params(dims: Dict, seed: int) -> Dict:
    """All weights, layers stacked on a leading axis."""
    _, lkeys = layer_keys(dims, seed)
    layers = [init_layer(dims, k) for k in lkeys]
    out = init_outer(dims, seed)
    out["layers"] = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *layers)
    return out


def batch_at(seed: int, step: int, batch: int, seq: int, vocab: int,
             noise: float = 0.05) -> Dict[str, jnp.ndarray]:
    """Training rows of one step: sequence ``step * batch + row`` is an
    affine-quadratic token recurrence with 5% of tokens drawn at random,
    from the seed's key folded with the sequence's index."""
    base = jax.random.PRNGKey(seed)

    def seq_of(sid):
        k = jax.random.fold_in(base, sid)
        k1, k2, k3 = jax.random.split(k, 3)
        a = 3 + 2 * jax.random.randint(k1, (), 0, 8)
        c = jax.random.randint(k2, (), 1, vocab)
        t0 = jax.random.randint(k3, (), 0, vocab)
        idx = jnp.arange(seq + 1, dtype=jnp.int32)
        toks = jnp.mod(t0 + idx * a + (idx * idx) * c, vocab)
        kn1, kn2 = jax.random.split(jax.random.fold_in(k, 7))
        flip = jax.random.uniform(kn1, (seq + 1,)) < noise
        rand = jax.random.randint(kn2, (seq + 1,), 0, vocab)
        return jnp.where(flip, rand, toks)

    ids = jnp.int32(step) * batch + jnp.arange(batch, dtype=jnp.int32)
    toks = jax.vmap(seq_of)(ids)
    return {"tokens": toks[:, :-1].astype(jnp.int32),
            "targets": toks[:, 1:].astype(jnp.int32)}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _mm(a, b, quant: Optional[str], spec: str = "...d,df->...f"):
    if quant:
        a = a.astype(quant).astype(jnp.float32)
        b = b.astype(quant).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _f32(x):
    return x.astype(jnp.float32)


def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + _f32(scale))


def rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: int, quant):
    """q (S,H,D), k/v (S,KV,D): causal attention, keys at most ``window``
    positions back, in blocks of queries."""
    S, H, D = q.shape
    KV = k.shape[1]
    g = H // KV
    blk = min(Q_BLOCK, S)
    nb = -(-S // blk)
    pad = nb * blk - S
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, blk, KV, g, D)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(i, qb):
        qpos = i * blk + jnp.arange(blk)
        s = _mm(qb, k, quant, "qkgd,skd->kgqs") / math.sqrt(D)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm(p, v, quant, "kgqs,skd->qkgd")

    out = jax.lax.map(lambda a: block(*a), (jnp.arange(nb), qp))
    return out.reshape(nb * blk, H, D)[:S]


def layer(p, x, pos, dims, quant):
    h, kv, hd = dims["heads"], dims["kv_heads"], dims["head_dim"]
    S = x.shape[0]
    y = rmsnorm(x, p["ln1"], dims["norm_eps"])
    q = _mm(y, _f32(p["wq"]), quant).reshape(S, h, hd)
    k = _mm(y, _f32(p["wk"]), quant).reshape(S, kv, hd)
    v = _mm(y, _f32(p["wv"]), quant).reshape(S, kv, hd)
    q = rope(q, pos, dims["rope_theta"])
    k = rope(k, pos, dims["rope_theta"])
    o = attention(q, k, v, dims["window"], quant).reshape(S, h * hd)
    x = x + _mm(o, _f32(p["wo"]), quant)
    y = rmsnorm(x, p["ln2"], dims["norm_eps"])
    a = _mm(y, _f32(p["gate"]), quant)
    b = _mm(y, _f32(p["up"]), quant)
    return x + _mm(jax.nn.silu(a) * b, _f32(p["down"]), quant)


def seq_loss(params, tokens, targets, dims, quant=None):
    """Mean cross-entropy of one sequence."""
    x = _f32(params["embed"][tokens])
    pos = jnp.arange(tokens.shape[0])

    def body(x, p):
        return jax.checkpoint(
            lambda p, x: layer(p, x, pos, dims, quant))(p, x), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(x, params["final_norm"], dims["norm_eps"])
    logits = _mm(x, _f32(params["head"]), quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - ll)


# ---------------------------------------------------------------------------
# training: the first steps of AdamW
# ---------------------------------------------------------------------------

ADAM = {"lr": 3e-4, "warmup": 100, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
        "weight_decay": 0.1, "clip": 1.0}


def lr_at(step: int) -> float:
    return ADAM["lr"] * step / ADAM["warmup"]


def _grads_fn(dims, quant, rows):
    """jit: (params, tokens, targets) -> (mean loss, f32 grads) over the
    first ``rows`` rows, one sequence at a time."""
    vg = jax.value_and_grad(
        lambda p, t, y: seq_loss(p, t, y, dims, quant))

    def fn(params, tokens, targets):
        # gradients with respect to float32 copies: a bfloat16 primal
        # would round its cotangent to bfloat16
        p32 = jax.tree_util.tree_map(_f32, params)

        def one(carry, xs):
            lsum, gsum = carry
            loss, g = vg(p32, *xs)
            return (lsum + loss, jax.tree_util.tree_map(
                jnp.add, gsum, g)), None

        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (lsum, gsum), _ = jax.lax.scan(
            one, (jnp.float32(0), g0), (tokens[:rows], targets[:rows]))
        return lsum / rows, jax.tree_util.tree_map(lambda g: g / rows, gsum)

    return jax.jit(fn)


def _adam_fn():
    b1, b2, eps, wd, clip = (ADAM[k] for k in
                             ("b1", "b2", "eps", "weight_decay", "clip"))

    def fn(params, m, v, grads, t, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                          for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def upd(p, m, v, g):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * _f32(p)
            return (_f32(p) - lr * u).astype(p.dtype), m, v

        out = jax.tree_util.tree_map(upd, params, m, v, grads)
        pick = lambda i: jax.tree_util.tree_map(
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    return jax.jit(fn, donate_argnums=(0, 1, 2))


def train_readings(dims: Dict, seed: int, batch: int, seq: int,
                   steps: int = 3, quant: Optional[str] = None,
                   rows: Optional[int] = None) -> Dict:
    """Run the first ``steps`` steps from the seed's weights and rows.

    Returns the loss of each step, the first gradient as the optimizer
    takes it (``m / (1 - b1)`` after step 0: clipped), and the
    parameters after the last step, each as host arrays keyed like
    ``flat_keys``.  ``rows`` < batch takes the loss over the first rows
    only (the half-batch fault)."""
    params = init_params(dims, seed)
    p0 = {k: np.asarray(v) for k, v in flat(params).items()}
    m = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32),
                               params)
    v = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32),
                               params)
    grads_fn = _grads_fn(dims, quant, rows or batch)
    adam = _adam_fn()
    losses, grad0 = [], None
    for s in range(steps):
        b = batch_at(seed, s, batch, seq, dims["vocab"])
        loss, g = grads_fn(params, b["tokens"], b["targets"])
        losses.append(float(loss))
        params, m, v = adam(params, m, v, g, jnp.float32(s + 1),
                            jnp.float32(lr_at(s)))
        del g
        if s == 0:
            grad0 = {k: np.asarray(x) / (1 - ADAM["b1"])
                     for k, x in flat(m).items()}
    p3 = {k: np.asarray(x) for k, x in flat(params).items()}
    return {"losses": losses, "grad0": grad0, "params0": p0,
            "params": p3}


def flat(params: Dict) -> Dict[str, jnp.ndarray]:
    """One entry per weight matrix or vector, each layer on its own."""
    out = {k: params[k] for k in ("embed", "final_norm", "head")}
    n = params["layers"]["wq"].shape[0]
    for name, x in params["layers"].items():
        for i in range(n):
            out[f"layers.{i}.{name}"] = x[i]
    return out


# ---------------------------------------------------------------------------
# serving: how far each served token lies below the best one
# ---------------------------------------------------------------------------

def _serve_fns(dims, quant):
    def embed(table, tokens):
        return _f32(table[tokens])

    def one_layer(p, x):
        return layer(p, x, jnp.arange(x.shape[0]), dims, quant)

    def head(x, norm, w):
        return _mm(rmsnorm(x, norm, dims["norm_eps"]), _f32(w), quant)

    return jax.jit(embed), jax.jit(one_layer), jax.jit(head)


def serve_gaps(dims: Dict, seed: int, sequences: List[np.ndarray],
               n_prompt: List[int], controls=()) -> List[Dict]:
    """For each sequence (prompt followed by its served tokens), at every
    position that produced a served token: ``served``, how far the
    float32 logit of the served token lies below the float32 best; and
    for each precision in ``controls``, how far the token that precision
    puts first lies below the float32 best.  Runs layer by layer: each
    layer's weights are made from the seed when needed and dropped after,
    so one layer and the activations are all that is resident."""
    quants = (None,) + tuple(controls)
    fns = {q: _serve_fns(dims, q) for q in quants}
    outer = init_outer(dims, seed)
    _, lkeys = layer_keys(dims, seed)
    xs = {q: [fns[q][0](outer["embed"], jnp.asarray(s, jnp.int32))
              for s in sequences] for q in quants}
    for i in range(dims["layers"]):
        p = init_layer(dims, lkeys[i])
        for q in quants:
            xs[q] = [fns[q][1](p, x) for x in xs[q]]
        del p
    out = []
    for j, (seq, P) in enumerate(zip(sequences, n_prompt)):
        rows = slice(P - 1, len(seq) - 1)
        ref = fns[None][2](xs[None][j][rows], outer["final_norm"],
                           outer["head"])
        best = jnp.max(ref, axis=-1)
        served = jnp.asarray(seq[P:], jnp.int32)
        got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        res = {"served": np.asarray(best - got)}
        for q in controls:
            lq = fns[q][2](xs[q][j][rows], outer["final_norm"],
                           outer["head"])
            top = jnp.argmax(lq, axis=-1)
            res[q] = np.asarray(best - jnp.take_along_axis(
                ref, top[:, None], axis=-1)[:, 0])
        out.append(res)
    return out


# ---------------------------------------------------------------------------
# the program's weights under the reference's names
# ---------------------------------------------------------------------------

def program_weights(params) -> Dict[str, np.ndarray]:
    """The program's parameter tree as the reference's weight names."""
    blk = params["groups"][0][0]
    out = {"embed": params["embed"]["table"],
           "final_norm": params["final_norm"]["scale"],
           "head": params["head"]["w"]}
    parts = {"ln1": blk["ln1"]["scale"], "ln2": blk["ln2"]["scale"],
             "wq": blk["attn"]["wq"]["w"], "wk": blk["attn"]["wk"]["w"],
             "wv": blk["attn"]["wv"]["w"], "wo": blk["attn"]["wo"]["w"],
             "gate": blk["ffn"]["gate"]["w"], "up": blk["ffn"]["up"]["w"],
             "down": blk["ffn"]["down"]["w"]}
    for name, x in parts.items():
        for i in range(x.shape[0]):
            out[f"layers.{i}.{name}"] = x[i]
    return {k: np.asarray(v) for k, v in out.items()}


# ---------------------------------------------------------------------------
# operations the work needs, from shapes alone
# ---------------------------------------------------------------------------

def layer_matmul_params(dims: Dict) -> int:
    d, h, kv, hd, ff = (dims["d_model"], dims["heads"], dims["kv_heads"],
                        dims["head_dim"], dims["d_ff"])
    return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * ff


def matmul_params(dims: Dict) -> int:
    """Weights that a token multiplies: the layers and the head (the
    embedding is a lookup)."""
    return dims["layers"] * layer_matmul_params(dims) \
        + dims["d_model"] * dims["vocab"]


def attended_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of causal attention over ``seq`` positions,
    keys at most ``window`` back."""
    w = window or seq
    if seq <= w:
        return seq * (seq + 1) // 2
    return w * (w + 1) // 2 + (seq - w) * w


def attn_flops_per_pair(dims: Dict) -> int:
    """Scores and the weighted sum: 2 x 2 x head_dim per head."""
    return 4 * dims["heads"] * dims["head_dim"]


def train_flops(dims: Dict, batch: int, seq: int) -> float:
    """One training step of ``batch`` sequences: forward and backward
    (three times the forward)."""
    fwd = 2 * matmul_params(dims) * batch * seq \
        + attn_flops_per_pair(dims) * dims["layers"] * batch \
        * attended_pairs(seq, dims["window"])
    return 3.0 * fwd


def prefill_flops(dims: Dict, prompt: int) -> float:
    """A prompt's prefill: every position through the layers, the head
    for the last position only."""
    head = dims["d_model"] * dims["vocab"]
    return 2.0 * (matmul_params(dims) - head) * prompt + 2.0 * head \
        + attn_flops_per_pair(dims) * dims["layers"] \
        * attended_pairs(prompt, dims["window"])


def decode_flops(dims: Dict, tokens: int, context: int) -> float:
    """``tokens`` decoded tokens whose attended contexts add up to
    ``context`` positions."""
    return 2.0 * matmul_params(dims) * tokens \
        + attn_flops_per_pair(dims) * dims["layers"] * context


def serve_flops(dims: Dict, work: Dict) -> float:
    return decode_flops(dims, work["decode_tokens"],
                        work["decode_context"]) \
        + sum(prefill_flops(dims, p) for p in work["prefill"])
