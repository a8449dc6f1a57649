"""Plain reference for a Llama-style decoder trained by gossip: float32,
``precision="highest"``, no kernels, no batching across workers, no
rematerialisation tricks beyond one checkpoint per layer to bound memory.

It follows the published equations (Llama: RMSNorm, rotary embedding with
rotate-half, grouped-query causal attention, SwiGLU, tied output head,
next-token cross-entropy), AdamW as the configuration states it, and the
gossip step x_i <- sum_j W_ij x_j (adapt, then combine). It imports nothing
of the program and takes nothing the program made except the topology's
edge list and edge weights, the answer of the solve the training job runs
on, from which it builds W itself in float64.

Parameter layout: the tree the program's step consumes (``embed``,
``final_norm``, ``layers/{ln1, attn/{wq, wk, wv, wo}, ln2, mlp/{w_gate,
w_up, w_down}}``, layers stacked on a leading axis). A norm leaf holds an
offset s around 1: the RMSNorm gain is 1 + s.

The weights are made here, from the seed, in one jitted call; the harness
hands them to the program in the type they are trained in, and the reference
makes them again after the window.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # float8_e4m3fn's largest finite value


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------

def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // hq
    return {"d": d, "hq": hq, "hkv": cfg["num_key_value_heads"], "hd": hd,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"])}


def leaf_shapes(cfg: dict) -> dict:
    k = dims(cfg)
    d, L = k["d"], k["L"]
    q, kv = k["hq"] * k["hd"], k["hkv"] * k["hd"]
    return {
        "embed": (k["v"], d),
        "final_norm": (d,),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
                     "wo": (L, q, d)},
            "mlp": {"w_gate": (L, d, k["f"]), "w_up": (L, d, k["f"]),
                    "w_down": (L, k["f"], d)},
        },
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (64-bit and larger included)."""
    lo, hi = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo) >> 1), int(hi) >> 1)


def _init_one(key, cfg: dict, dtype):
    init = cfg.get("init", {})
    emb_std = float(init.get("embed_std", 0.02))
    norm_std = float(init.get("norm_scale_std", 0.0))
    shapes = leaf_shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]
    keys = jax.random.split(key, len(paths))
    leaves = []
    for (path, shape), k in zip(paths, keys):
        name = jax.tree_util.keystr(path)
        z = jax.random.normal(k, shape, jnp.float32)
        if "embed" in name:
            x = z * emb_std
        elif len(shape) <= 2 and ("norm" in name or "ln" in name):
            x = z * norm_std
        else:                              # (L, fan_in, fan_out)
            x = z / float(np.sqrt(shape[-2]))
        leaves.append(x.astype(dtype))
    treedef = jax.tree_util.tree_structure(shapes, is_leaf=_is_shape)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def init_stacked(seed: int, cfg: dict, workers: int, dtype=jnp.bfloat16,
                 sharding=None):
    """Every worker's weights, stacked on a leading axis, in one jitted call;
    each worker draws its own, as replicas that have drifted apart, so that
    every row of the mixing matrix shows in the parameters."""

    def make(key):
        ks = jax.random.split(key, workers)
        return jax.vmap(lambda k: _init_one(k, cfg, dtype))(ks)

    return jax.jit(make, out_shardings=sharding)(seed_key(seed))


def mixing_matrix(n: int, edges, g) -> np.ndarray:
    """W = I - sum_e g_e (e_i - e_j)(e_i - e_j)^T in float64."""
    W = np.eye(n)
    for (i, j), w in zip(edges, np.asarray(g, np.float64)):
        W[i, i] -= w
        W[j, j] -= w
        W[i, j] += w
        W[j, i] += w
    return W


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def fp8_round(x):
    """The value of ``x`` rounded to float8_e4m3fn under a per-tensor scale
    (the amax maps to the format's largest value), back in float32: the
    lower-precision control's matmul inputs. The gradient passes straight
    through, so the backward pass multiplies the rounded values in float32,
    as an fp8 recipe with scaled float32 gradients would."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + s)


def _rope(x, theta):
    """Rotate-half rotary embedding over positions 0..S-1; x (B,S,H,hd)."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs       # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(params, tokens, labels, cfg: dict, quant: bool = False):
    """Mean next-token cross-entropy over the labelled positions of one
    worker's batch; every matmul input rounded to fp8 when ``quant``."""
    k = dims(cfg)
    r = fp8_round if quant else (lambda a: a)

    def mm(a, b):
        return jnp.matmul(r(a), r(b), precision=HIGHEST)

    B, S = tokens.shape
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))
    group = k["hq"] // k["hkv"]

    def layer(x, lp):
        h = _rms(x, lp["ln1"], k["eps"])
        q = mm(h, lp["attn"]["wq"]).reshape(B, S, k["hq"], k["hd"])
        kk = mm(h, lp["attn"]["wk"]).reshape(B, S, k["hkv"], k["hd"])
        v = mm(h, lp["attn"]["wv"]).reshape(B, S, k["hkv"], k["hd"])
        q, kk = _rope(q, k["theta"]), _rope(kk, k["theta"])
        kk = jnp.repeat(kk, group, axis=2)          # query head h reads kv head h // group
        v = jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(kk), precision=HIGHEST)
        s = jnp.where(causal, s / float(np.sqrt(k["hd"])), -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", r(p), r(v), precision=HIGHEST)
        x = x + mm(o.reshape(B, S, k["hq"] * k["hd"]), lp["attn"]["wo"])
        h = _rms(x, lp["ln2"], k["eps"])
        g = mm(h, lp["mlp"]["w_gate"])
        u = mm(h, lp["mlp"]["w_up"])
        return x + mm(jax.nn.silu(g) * u, lp["mlp"]["w_down"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = _rms(x, params["final_norm"], k["eps"])
    logits = mm(x, params["embed"].T)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - tgt) * valid) / jnp.maximum(jnp.sum(valid), 1)


def leaf_norms(tree) -> jnp.ndarray:
    """(leaves,) float32 L2 norms of one worker's tree."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


# ---------------------------------------------------------------------------
# gossip training rounds
# ---------------------------------------------------------------------------

def train_rounds(params0, batches, W: np.ndarray, cfg: dict, rounds: int, *,
                 quant: bool = False, half_batch: bool = False):
    """``rounds`` gossip rounds from ``params0`` (stacked on a leading
    worker axis, in the type they were made; computed in float32) on
    ``batches[t] = (tokens, labels)`` (host arrays, (n, b, S)).

    Returns ``{"loss": [mean over workers per round], "grad_norm": (leaves,
    n) norms of the first round's gradients, "change_norm": (leaves, n)
    norms of params after ``rounds`` minus params0}``, as host arrays.
    ``half_batch`` masks the second half of every row's labels, a fault
    planted for calibration. Workers are updated one at a time and mixed
    leaf by leaf, so that the float32 state is all that stays resident."""
    opt = cfg["optimizer"]
    b1, b2, eps = float(opt["b1"]), float(opt["b2"]), float(opt["eps"])
    lr, wd = float(opt["lr"]), float(opt["weight_decay"])
    n = np.asarray(W).shape[0]
    Wd = jnp.asarray(W, jnp.float32)

    @jax.jit
    def worker(p, mu, nu, t, tokens, labels):
        loss, g = jax.value_and_grad(loss_fn)(p, tokens, labels, cfg, quant)
        c1 = 1.0 - b1 ** t
        c2 = 1.0 - b2 ** t
        mu = jax.tree.map(lambda m, gi: b1 * m + (1 - b1) * gi, mu, g)
        nu = jax.tree.map(lambda v, gi: b2 * v + (1 - b2) * gi * gi, nu, g)
        p = jax.tree.map(
            lambda x, m, v: x - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * x), p, mu, nu)
        return loss, leaf_norms(g), p, mu, nu

    mix_leaf = jax.jit(lambda x: jnp.einsum("ij,j...->i...", Wd, x,
                                            precision=HIGHEST))
    start = jax.jit(lambda tree, i: jax.tree.map(
        lambda x: x[i].astype(jnp.float32), tree))
    change = jax.jit(lambda a, tree, i: leaf_norms(jax.tree.map(
        lambda x, y: x - y[i].astype(jnp.float32), a, tree)))

    p = [start(params0, i) for i in range(n)]
    mu = [jax.tree.map(jnp.zeros_like, p[0]) for _ in range(n)]
    nu = [jax.tree.map(jnp.zeros_like, p[0]) for _ in range(n)]
    losses, gnorm = [], None
    for t in range(1, rounds + 1):
        tokens, labels = batches[t - 1]
        if half_batch:
            labels = np.array(labels)
            labels[..., labels.shape[-1] // 2:] = -100
        loss_t, gn = [], []
        for i in range(n):
            li, gi, p[i], mu[i], nu[i] = worker(
                p[i], mu[i], nu[i], jnp.float32(t), jnp.asarray(tokens[i]),
                jnp.asarray(labels[i]))
            loss_t.append(float(li))
            gn.append(np.asarray(gi))
        losses.append(float(np.mean(loss_t)))
        if t == 1:
            gnorm = np.stack(gn, axis=1)
        treedef = jax.tree.structure(p[0])
        cols = [jax.tree.leaves(pi) for pi in p]
        p = None
        mixed = []
        for k in range(len(cols[0])):
            m = mix_leaf(jnp.stack([c[k] for c in cols]))
            for c in cols:
                c[k] = None
            mixed.append([m[i] for i in range(n)])
            del m
        p = [jax.tree.unflatten(treedef, [mixed[k][i] for k in range(len(mixed))])
             for i in range(n)]
        del cols, mixed
    cn = np.stack([np.asarray(change(p[i], params0, i)) for i in range(n)],
                  axis=1)
    return {"loss": losses, "grad_norm": gnorm, "change_norm": cn}
