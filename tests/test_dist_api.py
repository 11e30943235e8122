"""Unit coverage for the rebuilt `repro.dist` layer: tree_shardings
round-trip on a host-device mesh, elastic batch rebalance, gradient
compression schemes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as sh
from repro.dist.compression import ErrorFeedback, compression_ratio
from repro.dist.elastic import ElasticMembership, Member, split_batch
from repro.launch.mesh import make_mesh


# ------------------------------------------------------------------ sharding
def test_tree_shardings_roundtrip():
    mesh = make_mesh((1, 1), ("data", "model"))
    axes = {"wq": ("embed", "heads", None), "scale": ("embed",),
            "tok": ("batch", "seq")}
    specs = {"wq": jax.ShapeDtypeStruct((8, 4, 2), jnp.float32),
             "scale": jax.ShapeDtypeStruct((8,), jnp.float32),
             "tok": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    shardings = sh.tree_shardings(mesh, axes, sh.MEGATRON_RULES, specs)
    assert set(shardings) == {"wq", "scale", "tok"}
    assert all(isinstance(s, jax.sharding.NamedSharding)
               for s in shardings.values())
    assert shardings["wq"].spec == P(None, "model", None)
    assert shardings["tok"].spec == P("data", None)
    # the shardings place actual arrays (round-trip through device_put)
    x = jax.device_put(jnp.zeros((8, 4, 2)), shardings["wq"])
    assert x.shape == (8, 4, 2)


def test_rule_sets_registry_consistent():
    assert set(sh.RULE_SETS) == {"megatron", "decode", "ep", "dp", "dpep",
                                 "fsdp"}
    for rules in sh.RULE_SETS.values():
        for v in rules.values():
            assert v is None or isinstance(v, (str, tuple))


def test_constrain_identity_outside_context():
    x = jnp.ones((4, 8))
    assert sh.constrain(x, "batch", "embed") is x


def test_spec_with_shape_applies_divisibility():
    am = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
    assert sh.spec(("batch", "heads"), sh.MEGATRON_RULES, am,
                   shape=(6, 4)) == P(None, "model")


# ------------------------------------------------------------------- elastic
def test_split_batch_remainder_goes_first():
    assert split_batch(10, [7, 3, 5]) == {7: 4, 3: 3, 5: 3}
    assert split_batch(6, []) == {}


def test_membership_epoch_sequence():
    m = ElasticMembership([Member(0), Member(1), Member(2)], global_batch=10)
    e0 = m.current_epoch()
    assert e0.number == 0 and sum(e0.batch_of.values()) == 10
    e1 = m.revoke(1)
    assert e1.number == 1 and sorted(e1.batch_of.values()) == [5, 5]
    e2 = m.join(Member(9, gpu="k80"))
    assert e2.number == 2 and sum(e2.batch_of.values()) == 10
    assert {mm.id for mm in e2.members} == {0, 2, 9}
    with pytest.raises(KeyError):
        m.revoke(1)          # already gone
    with pytest.raises(KeyError):
        m.join(Member(9))    # already present
    assert 0 in m and 1 not in m  # __contains__ (trainer staleness guard)


# --------------------------------------------------------------- compression
@pytest.mark.parametrize("scheme,max_rel_err", [("none", 0.0),
                                                ("bf16", 0.01),
                                                ("int8", 0.02)])
def test_compression_schemes_bounded_error(scheme, max_rel_err):
    rng = np.random.default_rng(1)
    g = {"w": jnp.asarray(rng.normal(size=256), jnp.float32)}
    ef = ErrorFeedback(scheme)
    res = ef.init(g)
    d, new_res = ef.roundtrip(g, res)
    err = float(jnp.linalg.norm(d["w"] - g["w"]) / jnp.linalg.norm(g["w"]))
    assert err <= max_rel_err
    # residual + applied reconstructs the corrected gradient exactly
    np.testing.assert_allclose(np.asarray(d["w"] + new_res["w"]),
                               np.asarray(g["w"]), rtol=1e-5, atol=1e-6)


def test_compression_ratio_and_unknown_scheme():
    assert compression_ratio("none") == 1.0
    assert compression_ratio("int8") == 0.25
    with pytest.raises(ValueError):
        ErrorFeedback("fp4")
