"""Plain float32 reference for DeepSeek-V2's block on one chip's share of an
expert-parallel layer, with its seeded weights and its training step.

Nothing here imports the program. The weights are made from the seed by
this file, handed to the program, and made again here for the reference.
The layout of the weight tree is the program's (`embed`, `final_norm`,
`lm_head`, a `dense_layers` stack of `ln1`, `attn`, `ln2`, `mlp`, a
`layers` stack of `ln1`, `attn`, `ln2`, `moe`), written out below, so a
program that renames a leaf fails loudly here.

The step follows the published DeepSeek-V2 modelling code:

* multi-head latent attention without query compression: q from one
  projection, split into a no-position part (128) and a rotary part (64);
  the key-value latent (512) from a down-projection, RMS-normed, then
  up-projected to per-head keys (128) and values (128); one rotary key
  (64) shared by the heads, from its own projection of the input;
* YaRN rotary positions: each inverse frequency blended between the
  original and the original over `factor` by a linear ramp between the
  correction dims of `beta_fast` and `beta_slow` rotations over
  `original_max_position_embeddings`; cos and sin times
  mscale(factor, mscale) / mscale(factor, mscale_all_dim); the softmax
  scale 1/sqrt(192) times mscale(factor, mscale_all_dim)^2, with
  mscale(s, m) = 0.1 m ln s + 1;
* causal softmax attention, output projection, residual;
* the first `first_k_dense_replace` layers with a SwiGLU MLP of
  `intermediate_size`; the others with a softmax router over all
  `expert_parallel.router_experts` experts, greedy top-k, weights not
  renormalised (`norm_topk_prob` false) and scaled by
  `routed_scaling_factor`, the SwiGLU experts of `moe_intermediate_size`
  held here (`n_routed_experts` of them, from
  `expert_parallel.first_held_expert`), each weighted by its gate where the
  token chose it, and the `n_shared_experts` shared experts as one SwiGLU
  MLP of their summed width; every token's every assignment counts;
* the sequence-wise balance loss (`seq_aux`): per sequence, each expert's
  share of the top-k assignments times E, times its mean probability,
  summed over the experts, averaged over the sequences, times
  `aux_loss_alpha`, added to the loss for every expert layer;
* a final RMSNorm, the untied head, mean token cross-entropy.

Then global-norm clipping and AdamW, as `dense_lm`. Departures from the
published code, none of which changes the mathematics on seeded weights:

* the rotary dims are rotated in halves (the first 32 with the last 32),
  where the published code interleaves them (even with odd): a fixed
  permutation of the rotary columns of the q and rotary-key projections;
* the key-value down-projection and the rotary-key projection are two
  matrices, the published `kv_a_proj_with_mqa` one; the up-projection
  likewise two, `kv_b_proj` one;
* the experts this chip does not hold are left out, here as in the
  program: this chip's share of the layer is what goes on to the next.

All in float32 with matmuls at `highest` precision, unless a rounding of
the matmul operands is passed in (the control). To fit on one chip beside
its optimizer state it is computed in blocks: each layer under
`jax.checkpoint`, attention a query chunk at a time, each chunk under
`jax.checkpoint` too. `dense_lm` keeps its AdamW update inside its jitted
step, with its own loss, so the same update is written out here; the token
feed, the seeds, the norms, the schedule, the float8 control and the
comparison are `dense_lm`'s.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from chipbench.configs import dense_lm
from chipbench.configs.dense_lm import (  # noqa: F401  (the driver's API)
    ROUNDINGS, TokenFeed, compare, key_of, leaf_norms, lr_at)

# query rows per attention block
CHUNK = 1024
# the embedding's init scale: token identity then outweighs what attention
# adds in every layer, and the router spreads the tokens about as a
# balance-trained router does. At 0.02, attention's average over the
# sequence (the same for every token) makes up a fifth to a third of each
# expert layer's normed input, and one expert takes most of the tokens.
EMBED_SCALE = 1.0


# ---------------------------------------------------------------- weights
def layout(c: dict) -> Dict:
    """(shape, init scale) per leaf; scale 0 marks a norm's ones."""
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rd, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"])
    Ld = c["first_k_dense_replace"]
    Lm = L - Ld
    F, f = c["intermediate_size"], c["moe_intermediate_size"]
    n, E = c["n_routed_experts"], c["expert_parallel"]["router_experts"]
    fs = c["n_shared_experts"] * f
    one = 0.0

    def attn(k):
        return {"wq": ((k, d, h, nope + rd), d ** -0.5),
                "wdkv": ((k, d, r), d ** -0.5),
                "wkrope": ((k, d, rd), d ** -0.5),
                "wuk": ((k, r, h, nope), r ** -0.5),
                "wuv": ((k, r, h, vd), r ** -0.5),
                "wo": ((k, h, vd, d), (h * vd) ** -0.5),
                "kv_norm": ((k, r), one)}

    def mlp(k, width):
        return {"wi": ((k, d, width), d ** -0.5),
                "wg": ((k, d, width), d ** -0.5),
                "wo": ((k, width, d), width ** -0.5)}

    def norms(k):
        return {"ln1": {"scale": ((k, d), one)},
                "ln2": {"scale": ((k, d), one)}}

    return {
        "embed": ((V, d), EMBED_SCALE),
        "final_norm": {"scale": ((d,), one)},
        "lm_head": ((d, V), d ** -0.5),
        "dense_layers": {**norms(Ld), "attn": attn(Ld), "mlp": mlp(Ld, F)},
        "layers": {**norms(Lm), "attn": attn(Lm),
                   "moe": {"router": ((Lm, d, E), d ** -0.5),
                           "wi": ((Lm, n, d, f), d ** -0.5),
                           "wg": ((Lm, n, d, f), d ** -0.5),
                           "wo": ((Lm, n, f, d), f ** -0.5),
                           "shared": mlp(Lm, fs)}},
    }


def _init(c: dict, key):
    specs, treedef = jax.tree.flatten(layout(c), is_leaf=dense_lm._is_spec)
    keys = jax.random.split(key, len(specs))
    leaves = [jnp.ones(shape, jnp.float32) if scale == 0.0 else
              scale * jax.random.normal(k, shape, jnp.float32)
              for k, (shape, scale) in zip(keys, specs)]
    return jax.tree.unflatten(treedef, leaves)


def json_key(c: dict) -> str:
    """A hashable key for a configuration, nested groups included."""
    return json.dumps(c, sort_keys=True)


def init_params(c: dict, seed: int):
    """The float32 weights of `seed`, made on the device in one call."""
    return jax.jit(functools.partial(_init, c))(key_of(seed))


def change_norms(c: dict, seed: int, params) -> Dict[str, float]:
    """Norm of each leaf's change from the weights of `seed`, which are
    made again inside the call rather than kept."""
    fn = _change_fn(json_key(c))
    return {k: float(v) for k, v in jax.device_get(
        fn(params, key_of(seed))).items()}


@functools.lru_cache(maxsize=None)
def _change_fn(ckey: str):
    c = json.loads(ckey)

    @jax.jit
    def fn(params, key):
        diff = jax.tree.map(lambda a, b: a - b, params, _init(c, key))
        return dense_lm._norms(diff)
    return fn


# ------------------------------------------------------------------ flops
def train_flops(c: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one train step over `batch` x `seq` tokens: 6 x
    matmul parameters x tokens for the latent attention's projections, the
    dense layer, the shared experts, the router and the head; the causal
    half of attention (scores over q/k's 192 dims, values over 128, 2 x 2
    x seq^2 / 2 per head forward, three times that with the backward
    pass); and the held experts' expected routed work, tokens x top-k x
    held / router experts. Recomputation is not counted."""
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rd, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"])
    Ld = c["first_k_dense_replace"]
    f = c["moe_intermediate_size"]
    E = c["expert_parallel"]["router_experts"]
    tokens = batch * seq
    attn_proj = d * h * (nope + rd) + d * (r + rd) + r * h * (nope + vd) \
        + h * vd * d
    dense = 3 * d * c["intermediate_size"]
    per_expert = 3 * d * f
    moe_layer = (c["n_shared_experts"] * per_expert + d * E
                 + per_expert * c["num_experts_per_tok"]
                 * c["n_routed_experts"] / E)
    params = V * d + L * attn_proj + Ld * dense + (L - Ld) * moe_layer
    attn = L * 3 * 2 * batch * seq * seq / 2 * h * (nope + rd + vd)
    return 6.0 * params * tokens + attn


# ------------------------------------------------------------------ model
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def yarn(c: dict, dim: int):
    """(inverse frequencies, cos/sin factor, softmax scale) of the
    configuration's rotary scaling, in closed form."""
    theta = float(c["rope_theta"])
    sc = c["rope_scaling"]
    s = float(sc["factor"])
    base = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def corr(rot):
        return (dim * math.log(sc["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(theta)))
    lo = max(math.floor(corr(sc["beta_fast"])), 0)
    hi = min(math.ceil(corr(sc["beta_slow"])), dim - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    inv = base / s * ramp + base * (1.0 - ramp)
    cs = _yarn_mscale(s, sc["mscale"]) / _yarn_mscale(s, sc["mscale_all_dim"])
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    scale = qk ** -0.5 * _yarn_mscale(s, sc["mscale_all_dim"]) ** 2
    return inv, cs, scale


def _rope(x, inv, cs):
    """x: (B, S, H, dim); rotate-half with the given inverse frequencies."""
    s, dim = x.shape[1], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = (jnp.cos(ang) * cs)[None, :, None]
    sin = (jnp.sin(ang) * cs)[None, :, None]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale, mm):
    """Causal softmax attention, a query chunk at a time, each chunk
    recomputed for the backward pass. q, k: (B, S, H, 192), v: (B, S, H,
    128)."""
    B, S = q.shape[:2]
    chunk = min(CHUNK, S)
    n = S // chunk
    qc = q.reshape(B, n, chunk, *q.shape[2:]).swapaxes(0, 1)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = mm("bqhk,bshk->bhqs", qi, k) * scale
        qpos = i * chunk + jnp.arange(chunk)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return mm("bhqs,bshv->bqhv", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(one, (jnp.arange(n), qc))
    return o.swapaxes(0, 1).reshape(B, S, *o.shape[3:])


def _mlp(y, m, mm):
    g = jax.nn.silu(mm("bsd,df->bsf", y, m["wg"]))
    return mm("bsf,fd->bsd", g * mm("bsd,df->bsf", y, m["wi"]), m["wo"])


def _experts(y, m, c: dict, mm):
    """The held experts' part of the routed result and the balance loss.
    Every held expert runs on every token; its output counts with the
    token's gate for it, zero where the token did not choose it."""
    k = c["num_experts_per_tok"]
    E = c["expert_parallel"]["router_experts"]
    first = c["expert_parallel"]["first_held_expert"]
    n = c["n_routed_experts"]
    probs = jax.nn.softmax(mm("bsd,de->bse", y, m["router"]), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    top_w = top_w * c["routed_scaling_factor"]
    held = first + jnp.arange(n)
    gate = jnp.sum(jnp.where(top_e[..., None, :] == held[:, None],
                             top_w[..., None, :], 0.0), -1)      # (B,S,n)
    g = jax.nn.silu(mm("bsd,edf->bsef", y, m["wg"]))
    h = g * mm("bsd,edf->bsef", y, m["wi"])
    out = mm("bse,bsed->bsd", gate, mm("bsef,efd->bsed", h, m["wo"]))
    # sequence-wise balance loss over all E router outputs
    S = y.shape[1]
    chosen = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), (1, 2))
    share = chosen / (S * k / E)                                  # (B,E)
    aux = jnp.mean(jnp.sum(share * jnp.mean(probs, 1), -1)) \
        * c["aux_loss_alpha"]
    return out + _mlp(y, m["shared"], mm), aux


def forward(params, tokens, c: dict, rnd=lambda x: x):
    """(logits, the expert layers' summed balance loss). `rnd` rounds
    every matmul operand."""
    eps = c["rms_norm_eps"]
    nope = c["qk_nope_head_dim"]
    inv, cs, scale = yarn(c, c["qk_rope_head_dim"])
    mm = lambda spec, a, b: jnp.einsum(spec, rnd(a), rnd(b))  # noqa: E731
    x = params["embed"][tokens]

    def block(x, lp, moe: bool):
        a = lp["attn"]
        y = _rms(x, lp["ln1"]["scale"], eps)
        q = mm("bsd,dhk->bshk", y, a["wq"])
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cs)],
                            -1)
        lat = _rms(mm("bsd,dr->bsr", y, a["wdkv"]), a["kv_norm"], eps)
        kr = _rope(mm("bsd,dk->bsk", y, a["wkrope"])[:, :, None], inv, cs)
        kn = mm("bsr,rhn->bshn", lat, a["wuk"])
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kr, kn.shape[:3] + kr.shape[3:])], -1)
        v = mm("bsr,rhv->bshv", lat, a["wuv"])
        o = _attention(q, k, v, scale, mm)
        x = x + mm("bshv,hvd->bsd", o, a["wo"])
        y = _rms(x, lp["ln2"]["scale"], eps)
        if moe:
            out, aux = _experts(y, lp["moe"], c, mm)
        else:
            out, aux = _mlp(y, lp["mlp"], mm), jnp.zeros((), jnp.float32)
        return x + out, aux

    x, _ = jax.lax.scan(jax.checkpoint(
        functools.partial(block, moe=False)), x, params["dense_layers"])
    x, auxs = jax.lax.scan(jax.checkpoint(
        functools.partial(block, moe=True)), x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return mm("bsd,dv->bsv", x, params["lm_head"]), jnp.sum(auxs)


def loss_fn(params, tokens, labels, c: dict, rnd=lambda x: x):
    """Mean next-token cross-entropy plus the expert layers' balance
    losses. `rnd` rounds every matmul operand."""
    logits, aux = forward(params, tokens, c, rnd)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold) + aux


# --------------------------------------------------------------- training
@functools.lru_cache(maxsize=None)
def _step_fn(ckey: str, tkey: str, rnd_name: str):
    c, tr = json.loads(ckey), json.loads(tkey)
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
        return loss, dense_lm._norms(g), params, m, v

    return jax.jit(step, donate_argnums=(0, 1, 2))


def train_readings(c: dict, tr: dict, seed: int, batches: List[dict],
                   rounding: str = "none",
                   rows: Optional[int] = None) -> dict:
    """Run the reference from the weights of `seed` over `batches`: each
    step's loss, the first (clipped) gradient's leaf norms, and the leaf
    norms of the weights' change after the last step.

    `rows` plants the fault of half of each batch left out: that many rows
    of each batch are kept. Half of a one-row batch is 0 rows; there the
    first half of the row's positions are kept, which, attention being
    causal, leaves out half of the batch's tokens. None: all."""
    fn = _step_fn(json_key(c), json_key(tr), rounding)
    params = init_params(c, seed)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad = [], None
    with jax.default_matmul_precision("highest"):
        for t, b in enumerate(batches):
            tok, lab = b["tokens"], b["labels"]
            if rows:
                tok, lab = tok[:rows], lab[:rows]
            elif rows is not None:
                half = tok.shape[1] // 2
                tok, lab = tok[:, :half], lab[:, :half]
            loss, gn, params, m, v = fn(params, m, v, jnp.float32(t),
                                        jnp.float32(lr_at(t, tr)),
                                        jnp.asarray(tok), jnp.asarray(lab))
            losses.append(float(loss))
            if grad is None:
                grad = {k: float(x) for k, x in jax.device_get(gn).items()}
        del m, v
        change = change_norms(c, seed, params)
    return {"loss": losses, "grad": grad, "change": change}
