"""DeepSeek-V2's block against its plain float32 reference
(`chipbench/configs/deepseek_v2_lm.py`) on seeded weights at a tiny size:
latent attention with YaRN, a dense layer, and expert layers that hold a
share of the routed experts, with dropless routing."""
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench.configs import deepseek_v2_lm as ref  # noqa: E402
from chipbench.drivers.train import model_config  # noqa: E402
from repro.configs import MoEConfig, RopeScaling, get_config  # noqa: E402
from repro.models import api  # noqa: E402
from repro.models import layers as L  # noqa: E402

CONFIG = os.path.join(ROOT, "chipbench", "configs",
                      "deepseek-v2-lite-L5-ep8.json")
SEED = 2 ** 31 + 77


def tiny(n_held=4, first_held=0, router_experts=8):
    """The benchmark's configuration file at d = 64, in float32: 2 dense +
    4 expert layers, `n_held` of `router_experts` routed experts held,
    top-2, one shared; YaRN over an original context of 16 positions."""
    with open(CONFIG) as f:
        c = json.load(f)
    c.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
             num_hidden_layers=6, first_k_dense_replace=2,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             n_routed_experts=n_held, num_experts_per_tok=2,
             n_shared_experts=1, vocab_size=256)
    c["expert_parallel"] = {"chips": router_experts // n_held,
                            "router_experts": router_experts,
                            "first_held_expert": first_held}
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=16)
    prog = c["program"]["model_config"]
    prog["mla"] = {"kv_lora_rank": 16, "q_lora_rank": 0,
                   "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                   "v_head_dim": 16}
    prog["moe"] = dict(prog["moe"], n_experts=router_experts, n_held=n_held,
                       first_held=first_held, top_k=2, n_shared_experts=1,
                       expert_d_ff=32)
    c["training"] = dict(c["training"], compute_dtype="float32")
    return c


def batch_of(c, rows=2, seq=64, seed=5):
    b = ref.TokenFeed(c["vocab_size"], seq, seed).batch(0, 0, 1, rows)
    return {k: jnp.asarray(v) for k, v in b.items()}


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


def test_config_file_maps_onto_the_program():
    c = tiny()
    cfg = model_config(c)
    hash(cfg)                                  # a jit static argument
    assert isinstance(cfg.moe, MoEConfig) and cfg.moe.held == 4
    assert isinstance(cfg.rope_scaling, RopeScaling)
    assert cfg.moe.norm_topk_prob is False and cfg.moe.seq_aux is True
    params = ref.init_params(c, SEED)
    assert jax.tree.structure(params) == jax.tree.structure(
        api.param_shapes(cfg))


def test_published_config_is_deepseek_v2_lite():
    cfg = get_config("deepseek-v2-lite-16b")
    assert cfg.norm_eps == 1e-6
    assert cfg.rope_scaling.factor == 40
    assert cfg.rope_scaling.mscale_all_dim == 0.707
    mo = cfg.moe
    assert (mo.n_experts, mo.top_k, mo.n_shared_experts, mo.held) == (
        64, 6, 2, 64)
    assert not mo.norm_topk_prob and mo.seq_aux
    assert mo.aux_loss_coef == 0.001


def test_logits_loss_and_gradients_match_the_reference():
    c = tiny()
    cfg = model_config(c)
    params = ref.init_params(c, SEED)
    b = batch_of(c)
    with jax.default_matmul_precision("highest"):
        (loss, counters), grads = jax.value_and_grad(
            api.loss_and_counters, has_aux=True)(params, cfg, b)
        want_loss, want_grads = jax.value_and_grad(ref.loss_fn)(
            params, b["tokens"], b["labels"], c)
        logits, aux = api.forward(params, cfg, b["tokens"])
        want_logits, want_aux = ref.forward(params, b["tokens"], c)
    assert rel(logits, want_logits) < 1e-5
    assert abs(float(aux) - float(want_aux)) < 1e-5 * float(want_aux)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    gaps = jax.tree.map(rel, grads, want_grads)
    assert max(jax.tree.leaves(gaps)) < 1e-4, gaps
    # 4 expert layers x 128 tokens x top-2, about half of it held here
    assert 0 < int(counters["moe_routed_held"]) < 4 * 128 * 2
    assert 0 < int(counters["moe_max_load"]) <= 128


def _layer(c, params, x):
    """The first expert layer's routed-plus-shared part, program and
    reference, on the same input."""
    cfg = model_config(c)
    lp = jax.tree.map(lambda v: v[0], params["layers"]["moe"])
    with jax.default_matmul_precision("highest"):
        y, aux, counters = L.moe(lp, cfg, x)
        want, want_aux = ref._experts(x, lp, c, jnp.einsum)
    return y, aux, counters, want, want_aux


def test_shares_sum_to_the_whole_layer():
    """Two chips of an EP = 2 layer: each holds 4 of the 8 experts. Their
    parts, with the shared experts (which both compute) counted once, add
    up to the reference's whole layer, all 8 experts held."""
    whole = tiny(n_held=8)
    params = ref.init_params(whole, SEED)
    lp = jax.tree.map(lambda v: v[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._experts(x, lp, whole, jnp.einsum)
        shared = ref._mlp(x, lp["shared"], jnp.einsum)
        parts = []
        for first in (0, 4):
            share = dict(lp, **{k: lp[k][first:first + 4]
                                for k in ("wi", "wg", "wo")})
            cfg = model_config(tiny(n_held=4, first_held=first))
            parts.append(L.moe(share, cfg, x)[0])
    assert rel(parts[0] + parts[1] - shared, want) < 1e-5
    # each share alone is the reference's share of the layer
    for first, part in zip((0, 4), parts):
        c = tiny(n_held=4, first_held=first)
        share = dict(lp, **{k: lp[k][first:first + 4]
                            for k in ("wi", "wg", "wo")})
        with jax.default_matmul_precision("highest"):
            assert rel(part, ref._experts(x, share, c, jnp.einsum)[0]) < 1e-5


def test_a_skewed_router_drops_no_assignment():
    """The router biased so that held expert 1 is in every token's top-2:
    it takes every token, four times an even share, and the layer still
    equals the reference, which computes every assignment."""
    c = tiny()
    params = ref.init_params(c, SEED)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64), jnp.float32)
    x = x.at[..., 0].set(4.0)
    router = params["layers"]["moe"]["router"].at[:, 0, 1].set(5.0)
    params["layers"]["moe"]["router"] = router
    y, aux, counters, want, want_aux = _layer(c, params, x)
    assert int(counters["moe_max_load"]) == 128
    probs = jax.nn.softmax(x.reshape(-1, 64) @ router[0], -1)
    top = jax.lax.top_k(probs, 2)[1]
    assert int(counters["moe_routed_held"]) == int(jnp.sum(top < 4))
    assert rel(y, want) < 1e-5
    assert abs(float(aux) - float(want_aux)) < 1e-5 * float(want_aux)


def test_yarn_frequencies_and_softmax_scale_in_closed_form():
    """DeepSeek-V2-Lite's YaRN: rotary dim 64, theta 1e4, factor 40 over
    4096 positions, beta 32 and 1. The correction dims are
    64 ln(4096 / (2 pi beta)) / (2 ln 1e4): 10.47 and 22.51, so the ramp
    runs from dim 10 (floor) to 23 (ceil)."""
    sc = get_config("deepseek-v2-lite-16b").rope_scaling
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    got = np.asarray(L.rope_freqs(64, 1e4, sc))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[9] == pytest.approx(base[9]) and got[23] == pytest.approx(
        base[23] / 40)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert L.softmax_scale(192, sc) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert L.softmax_scale(128, None) == 1 / math.sqrt(128)
    # the reference's closed form agrees, and cos/sin are not rescaled
    inv, cs, scale = ref.yarn({"rope_theta": 10000,
                               "rope_scaling": sc.__dict__,
                               "qk_nope_head_dim": 128,
                               "qk_rope_head_dim": 64}, 64)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-6)
    assert cs == 1.0 and scale == pytest.approx(L.softmax_scale(192, sc))


def test_chunked_attention_takes_a_scale_and_recomputes_each_chunk():
    """At Sq = 4 x chunk the scanned path, with its scale and its per-chunk
    recomputation, equals one unchunked block, value and gradient."""
    B, S, H, KV, hd, chunk = 1, 64, 4, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    scale = 0.3

    def chunked(q, k, v):
        return L._chunked_attn(q, k, v, True, 0, scale, chunk=chunk)

    def whole(q, k, v):
        return L._attn_block(q.reshape(B, S, KV, H // KV, hd), k, v, True,
                             0, 0, scale).reshape(B, S, H, hd)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(chunked(q, k, v), whole(q, k, v),
                                   rtol=1e-5, atol=1e-6)
        loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))  # noqa: E731
        g1 = jax.grad(loss(chunked), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(whole), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # the backward pass holds one chunk's scores: the scan body is
    # rematerialised
    jaxpr = str(jax.make_jaxpr(jax.grad(loss(chunked)))(q, k, v))
    assert "remat" in jaxpr or "checkpoint" in jaxpr


@pytest.mark.parametrize("Sq,causal,want,blocks", [
    (8192, True, tuple(range(1024, 8193, 1024)), 36),
    (8192, False, (8192,) * 8, 64),
    (512, True, (512,), 1),
])
def test_key_extents_leave_out_the_masked_blocks(Sq, causal, want, blocks):
    """At 8k with 1,024-query chunks the causal plan scores 36 of the 64
    key blocks; without the mask every block; Sq <= chunk is one block."""
    got = L.key_extents(Sq, Sq, causal, 0, 1024)
    assert got == want
    assert sum(-(-e // 1024) for e in got) == blocks


@pytest.mark.parametrize("causal", [True, False])
def test_chunks_with_key_extents_equal_one_block(causal):
    """At Sq = 4 x chunk each chunk, scored against its own key extent,
    gives what one unchunked block gives, value and gradient of q, k, v."""
    B, S, H, KV, hd, chunk = 1, 64, 4, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    scale = 0.3

    def chunked(q, k, v):
        return L._chunked_attn(q, k, v, causal, 0, scale, chunk=chunk)

    def whole(q, k, v):
        return L._attn_block(q.reshape(B, S, KV, H // KV, hd), k, v, causal,
                             0, 0, scale).reshape(B, S, H, hd)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(chunked(q, k, v), whole(q, k, v),
                                   rtol=1e-5, atol=1e-6)
        loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))  # noqa: E731
        g1 = jax.grad(loss(chunked), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(whole), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_first_chunk_scores_only_its_own_keys():
    """Under the causal mask chunk 0's score matmul takes `chunk` keys, not
    Sk; the last chunk takes all Sk."""
    B, S, H, KV, hd, chunk = 1, 64, 4, 2, 8, 16
    q = jnp.zeros((B, S, H, hd), jnp.float32)
    k = jnp.zeros((B, S, KV, hd), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: L._chunked_attn(q, k, v, True, 0, 0.3, chunk=chunk)
    )(q, k, k)

    def key_lengths(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                # the key (or value) operand: (B, extent, KV, hd)
                yield from (x.aval.shape[1] for x in eqn.invars
                            if x.aval.ndim == 4)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from key_lengths(sub)

    scored = list(key_lengths(jaxpr.jaxpr))
    assert scored[0] == chunk
    assert sorted(set(scored)) == [16, 32, 48, 64]


def test_short_attention_path_is_unchanged():
    """Sq <= chunk (Qwen3's 512-token path) is one block at 1/sqrt(hd),
    bit for bit, whether the scale is given or left to its default."""
    B, S, H, KV, hd = 2, 32, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    old = L._attn_block(q.reshape(B, S, KV, H // KV, hd), k, v, True, 0, 0,
                        1.0 / math.sqrt(hd)).reshape(B, S, H, hd)
    for out in (L._chunked_attn(q, k, v, True, 0),
                L._chunked_attn(q, k, v, True, 0, 1.0 / math.sqrt(hd))):
        assert np.array_equal(np.asarray(out, np.float32),
                              np.asarray(old, np.float32))
