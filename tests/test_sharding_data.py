"""Sharding rules + synthetic data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro.data.synthetic import SyntheticLM, SyntheticVision, host_shard
from repro.distributed.sharding import MeshPolicy, param_specs
from repro.models.lm import init_lm


def test_param_specs_rules():
    cfg = configs.get_smoke("qwen2-0.5b")
    params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    specs = param_specs(params, MeshPolicy())
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    d = {"/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path): s
         for path, s in flat}
    # embeddings: vocab on model
    assert d["embed/w"] == P("model", None)
    # WASI factored mlp up: L sharded on d_ff, R replicated
    up_l = [v for k, v in d.items() if k.endswith("mlp/up/L")]
    up_r = [v for k, v in d.items() if k.endswith("mlp/up/R")]
    assert all(s[-2:] == ("model", None) for s in up_l)
    assert all(tuple(s) == () or s[-2:] == (None, None) for s in up_r)
    # down: R sharded on input (d_ff)
    dn_r = [v for k, v in d.items() if k.endswith("mlp/down/R")]
    assert all(s[-2:] == (None, "model") for s in dn_r)
    # norms replicated
    norms = [v for k, v in d.items() if "ln1/scale" in k]
    assert all(tuple(s) == () for s in norms)


def test_stacked_leading_dims_not_sharded():
    cfg = configs.get_smoke("qwen2-0.5b")
    params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    specs = param_specs(params, MeshPolicy())
    for leaf_spec, leaf in zip(jax.tree.leaves(specs,
                                               is_leaf=lambda x: isinstance(x, P)),
                               jax.tree.leaves(params)):
        if len(leaf_spec) == leaf.ndim and leaf.ndim >= 3:
            assert leaf_spec[0] is None  # scan/stack dim unsharded


def test_synthetic_lm_deterministic_and_learnable_structure():
    data = SyntheticLM(vocab_size=64, seq_len=16, global_batch=4, seed=7)
    b1, b2 = data.batch(3), data.batch(3)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = data.batch(4)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))
    # labels are next tokens
    np.testing.assert_array_equal(np.asarray(b1["tokens"][:, 1:]),
                                  np.asarray(b1["labels"][:, :-1]))


def _jaxpr_avals(jaxpr):
    """Every intermediate aval of a jaxpr, nested sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield v.aval
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _jaxpr_avals(inner)


def test_synthetic_lm_full_vocab_has_no_dense_bigram_table():
    # qwen2-0.5b's vocabulary: a dense (V, V) f32 bigram table is 92 GB, so
    # the stream must draw bigram rows per token instead of indexing one
    v = 151936
    data = SyntheticLM(vocab_size=v, seq_len=4, global_batch=2, seed=3)
    jaxpr = jax.make_jaxpr(lambda: data.batch(0))()
    biggest = max(int(np.prod(a.shape)) for a in _jaxpr_avals(jaxpr.jaxpr)
                  if hasattr(a, "shape"))
    assert v <= biggest < v * v // 1000, biggest
    # a pure function of (seed, step): a second stream object agrees
    b1 = data.batch(0)
    b2 = SyntheticLM(vocab_size=v, seq_len=4, global_batch=2,
                     seed=3).batch(0)
    for k in ("tokens", "labels"):
        np.testing.assert_array_equal(np.asarray(b1[k]), np.asarray(b2[k]))
    toks = np.asarray(b1["tokens"])
    assert toks.shape == (2, 4) and toks.min() >= 0 and toks.max() < v


def test_host_shard_partitions_batch():
    data = SyntheticVision(n_classes=4, n_patches=8, patch_dim=6,
                           global_batch=8, seed=0)
    b = data.batch(0)
    parts = [host_shard(b, i, 4) for i in range(4)]
    got = np.concatenate([np.asarray(p["patches"]) for p in parts])
    np.testing.assert_array_equal(got, np.asarray(b["patches"]))


def test_host_shard_rejects_indivisible_batch():
    # a 7-row batch over 2 hosts must FAIL LOUDLY, not silently drop the
    # remainder row on every host (rows 6.. would never be trained on)
    data = SyntheticVision(n_classes=4, n_patches=8, patch_dim=6,
                           global_batch=7, seed=0)
    b = data.batch(0)
    with pytest.raises(ValueError, match="not divisible"):
        host_shard(b, 0, 2)
    # the message carries enough to debug: the offending shape and count
    with pytest.raises(ValueError, match=r"7.*process_count=2"):
        host_shard(b, 1, 2)


def test_collective_bytes_counts_combined_and_async_results():
    from repro.distributed.collectives import collective_bytes

    hlo = "\n".join([
        # combined all-reduce: a tuple result, every element is sent
        "%ar = (f32[8,4]{1,0}, bf16[16]{0}) all-reduce(%a, %b), "
        "replica_groups={}",
        # async all-gather: (operand, result) — only the result is new data
        "%ags = (f32[2,4]{1,0}, f32[8,4]{1,0}) all-gather-start(%c), "
        "dimensions={0}",
        "%ag = f32[8,4]{1,0} all-gather-done(%ags)",
        "%add = f32[8,4]{1,0} add(%ag, %ag)",
        # combined async all-gather: ((operands), (results))
        "%agc = ((bf16[2,4]{1,0}, f32[3]{0}), (bf16[8,4]{1,0}, f32[12]{0})) "
        "all-gather-start(%d, %e), dimensions={0}",
        # async permute: (operand, result, context, context)
        "%cps = (f32[4]{0}, f32[4]{0}, u32[], u32[]) "
        "collective-permute-start(%f), source_target_pairs={{0,1},{1,0}}",
    ])
    got = collective_bytes(hlo)
    assert got["all-reduce"] == 8 * 4 * 4 + 16 * 2
    assert got["all-gather"] == 8 * 4 * 4 + 8 * 4 * 2 + 12 * 4
    assert got["collective-permute"] == 4 * 4
    assert got["count"] == 4
    assert got["total"] == (got["all-reduce"] + got["all-gather"]
                            + got["collective-permute"])
