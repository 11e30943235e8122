"""Plain float32 reference for a decoder-only LM of the Qwen3 kind, with
its seeded weights, its token feed and its training step.

Nothing here imports the program. The weights and tokens are made from
the seed by this file, handed to the program, and made again here for the
reference. The layout of the weight tree is the program's (`embed`,
`final_norm`, a `layers` stack of `ln1`, `attn`, `ln2`, `mlp`), written
out below, so a program that renames a leaf fails loudly here.

The step follows the published Qwen3 block: RMSNorm, q/k/v projections,
per-head RMSNorm of q and k, rotary positions (rotate-half), causal
grouped-query softmax attention, output projection, residual, RMSNorm,
SwiGLU MLP, residual; a final RMSNorm and the tied embedding as the head;
mean token cross-entropy. Then global-norm clipping and AdamW with
decoupled weight decay on every leaf, under a linear warm-up and cosine
schedule. All in float32 with matmuls at `highest` precision, unless a
rounding of the matmul operands is passed in (the control).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List

import numpy as np

import jax
import jax.numpy as jnp


# ------------------------------------------------------------------ seeds
def key_of(seed: int):
    """A threefry key from any non-negative seed, however large."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


# ---------------------------------------------------------------- weights
def layout(c: dict) -> Dict:
    """(shape, init scale) per leaf; scale 0 marks a norm's ones."""
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    f = c["intermediate_size"]
    one = 0.0
    return {
        "embed": ((V, d), 0.02),
        "final_norm": {"scale": ((d,), one)},
        "layers": {
            "ln1": {"scale": ((L, d), one)},
            "ln2": {"scale": ((L, d), one)},
            "attn": {"wq": ((L, d, h, hd), d ** -0.5),
                     "wk": ((L, d, kv, hd), d ** -0.5),
                     "wv": ((L, d, kv, hd), d ** -0.5),
                     "wo": ((L, h, hd, d), (h * hd) ** -0.5),
                     "q_norm": ((L, hd), one),
                     "k_norm": ((L, hd), one)},
            "mlp": {"wi": ((L, d, f), d ** -0.5),
                    "wg": ((L, d, f), d ** -0.5),
                    "wo": ((L, f, d), f ** -0.5)},
        },
    }


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _init(c: dict, key):
    specs, treedef = jax.tree.flatten(layout(c), is_leaf=_is_spec)
    keys = jax.random.split(key, len(specs))
    leaves = [jnp.ones(shape, jnp.float32) if scale == 0.0 else
              scale * jax.random.normal(k, shape, jnp.float32)
              for k, (shape, scale) in zip(keys, specs)]
    return jax.tree.unflatten(treedef, leaves)


def init_params(c: dict, seed: int):
    """The float32 weights of `seed`, made on the device in one call."""
    return jax.jit(functools.partial(_init, c))(key_of(seed))


def leaf_norms(tree) -> Dict[str, float]:
    """Euclidean norm of every leaf, keyed by its path."""
    norms = _norms(tree)
    return {k: float(v) for k, v in jax.device_get(norms).items()}


@jax.jit
def _norms(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in flat}


def change_norms(c: dict, seed: int, params) -> Dict[str, float]:
    """Norm of each leaf's change from the weights of `seed`, which are
    made again inside the call rather than kept."""
    fn = _change_fn(json_key(c))
    return {k: float(v) for k, v in jax.device_get(
        fn(params, key_of(seed))).items()}


def json_key(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _change_fn(ckey):
    c = dict(ckey)

    @jax.jit
    def fn(params, key):
        diff = jax.tree.map(lambda a, b: a - b, params, _init(c, key))
        return _norms(diff)
    return fn


# ------------------------------------------------------------------- feed
def train_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one train step over `batch` x `seq` tokens, counted
    from shapes (`chipbench.flops`); the training driver reads it here, by
    the configuration's reference, so that a family of its own brings its
    own count."""
    from chipbench import flops
    return flops.dense_lm_train_flops(c, batch, seq)["total"]


class TokenFeed:
    """Zipf-like synthetic tokens, a pure function of (seed, step, shard):
    the same generator as the program's `SyntheticTokenSource`, kept here
    so that the reference reads the same rows without the program."""

    def __init__(self, vocab_size: int, seq_len: int, seed: int):
        self.vocab_size, self.seq_len, self.seed = vocab_size, seq_len, seed

    def batch(self, step: int, shard: int, n_shards: int,
              batch_per_shard: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, step, shard]))
        u = rng.random((batch_per_shard, self.seq_len + 1))
        v = self.vocab_size
        toks = ((v ** u - 1.0) / (v - 1.0) * (v - 1)).astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# ------------------------------------------------------------------ model
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, hd); rotate-half over the whole head."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(params, tokens, labels, c: dict,
            rnd: Callable = lambda x: x):
    """Mean next-token cross-entropy. `rnd` rounds every matmul operand."""
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    mm = lambda spec, a, b: jnp.einsum(spec, rnd(a), rnd(b))  # noqa: E731
    x = params["embed"][tokens]
    B, S, _ = x.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(x, lp):
        a = lp["attn"]
        y = _rms(x, lp["ln1"]["scale"], eps)
        q = _rms(mm("bsd,dhk->bshk", y, a["wq"]), a["q_norm"], eps)
        k = _rms(mm("bsd,dhk->bshk", y, a["wk"]), a["k_norm"], eps)
        v = mm("bsd,dhk->bshk", y, a["wv"])
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        s = mm("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("bhqs,bshk->bqhk", w, v)
        x = x + mm("bshk,hkd->bsd", o, a["wo"])
        y = _rms(x, lp["ln2"]["scale"], eps)
        m = lp["mlp"]
        g = jax.nn.silu(mm("bsd,df->bsf", y, m["wg"]))
        x = x + mm("bsf,fd->bsd", g * mm("bsd,df->bsf", y, m["wi"]),
                   m["wo"])
        return x, None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = mm("bsd,vd->bsv", x, params["embed"])
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


def lr_at(t: int, tr: dict) -> float:
    """Linear warm-up to `lr`, then cosine decay to a tenth of it."""
    w, total = tr["warmup_steps"], tr["total_steps"]
    warm = min(1.0, (t + 1.0) / max(1, w))
    prog = min(1.0, max(0.0, (t - w) / max(1, total - w)))
    return tr["lr"] * warm * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


@functools.lru_cache(maxsize=None)
def _step_fn(ckey, tkey, rnd_name: str):
    c, tr = dict(ckey), dict(tkey)
    rnd = ROUNDINGS[rnd_name]

    def step(params, m, v, t, lr, tokens, labels):
        loss, g = jax.value_and_grad(loss_fn)(params, tokens, labels, c, rnd)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, tr["grad_clip"] / (gn + 1e-9)), g)
        b1, b2, eps, wd = tr["b1"], tr["b2"], tr["eps"], tr["weight_decay"]
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1, c2 = 1 - b1 ** (t + 1.0), 1 - b2 ** (t + 1.0)
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                        + wd * p), params, m, v)
        return loss, _norms(g), params, m, v

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_readings(c: dict, tr: dict, seed: int, batches: List[dict],
                   rounding: str = "none", rows: int = 0) -> dict:
    """Run the reference from the weights of `seed` over `batches`: each
    step's loss, the first (clipped) gradient's leaf norms, and the leaf
    norms of the weights' change after the last step. `rows` keeps only
    that many rows of each batch (0: all)."""
    ckey, tkey = json_key(c), json_key(tr)
    fn = _step_fn(ckey, tkey, rounding)
    params = init_params(c, seed)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad = [], None
    with jax.default_matmul_precision("highest"):
        for t, b in enumerate(batches):
            tok, lab = b["tokens"], b["labels"]
            if rows:
                tok, lab = tok[:rows], lab[:rows]
            loss, gn, params, m, v = fn(params, m, v, jnp.float32(t),
                                        jnp.float32(lr_at(t, tr)),
                                        jnp.asarray(tok), jnp.asarray(lab))
            losses.append(float(loss))
            if grad is None:
                grad = {k: float(x) for k, x in jax.device_get(gn).items()}
        del m, v
        change = change_norms(c, seed, params)
    return {"loss": losses, "grad": grad, "change": change}


# --------------------------------------------------------------- controls
def _fp8_round(x):
    """Round to float8 e4m3 under a per-tensor scale: the value an fp8
    matmul operand holds."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


# the forward operands are rounded; the gradient passes through unrounded
_fp8 = jax.custom_vjp(_fp8_round)
_fp8.defvjp(lambda x: (_fp8_round(x), None), lambda _, g: (g,))

ROUNDINGS = {"none": lambda x: x, "fp8": _fp8}


# ------------------------------------------------------------- comparison
def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """Gaps of the program's readings from the reference's.

    loss_gap: the largest |loss - reference| / reference over the steps.
    grad_gap, change_gap: the worst leaf's |norm - reference norm| over
    the larger of that leaf's reference norm and the median leaf's. A leaf
    whose reference gradient is under a thousandth of the median leaf's
    moves by round-off alone and is left out of the change."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["loss"], ref["loss"]))
    med_g = float(np.median(list(ref["grad"].values())))
    grad_gap = max(abs(prog["grad"][k] - r) / max(r, med_g)
                   for k, r in ref["grad"].items())
    keep = [k for k, r in ref["grad"].items() if r >= 1e-3 * med_g]
    med_c = float(np.median([ref["change"][k] for k in keep]))
    change_gap = max(abs(prog["change"][k] - ref["change"][k])
                     / max(ref["change"][k], med_c) for k in keep)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}
