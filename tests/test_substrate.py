"""Substrate tests: optimizers, schedules, HLO analysis parser, data pipeline,
compressor sharding-safety."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compressors import qsgd_sharded
from repro.data.synthetic import SyntheticLMDataset, lm_batch_iterator
from repro.launch.hlo_analysis import CollectiveStats, _shape_bytes, collective_bytes
from repro.optim.optimizers import adamw, apply_updates, clip_by_global_norm, sgd
from repro.optim.schedules import cosine_schedule, linear_warmup
from repro.utils.tree import tree_dot, tree_norm


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------
def _quad_problem():
    params = {"w": jnp.array([3.0, -2.0]), "b": jnp.array([1.0])}
    grad = lambda p: {"w": 2 * p["w"], "b": 2 * p["b"]}  # f = ||p||^2
    return params, grad


def test_adamw_minimizes_quadratic():
    params, grad = _quad_problem()
    opt = adamw(lr=0.1, weight_decay=0.0)
    state = opt.init(params)
    for _ in range(200):
        updates, state = opt.update(grad(params), state, params)
        params = apply_updates(params, updates)
    assert float(tree_norm(params)) < 1e-2


def test_sgd_momentum_minimizes():
    params, grad = _quad_problem()
    opt = sgd(lr=0.05, momentum=0.9)
    state = opt.init(params)
    for _ in range(150):
        updates, state = opt.update(grad(params), state, params)
        params = apply_updates(params, updates)
    assert float(tree_norm(params)) < 1e-2


def test_weight_decay_mask():
    """Decay applies to matrices (ndim>=2) but not vectors by default."""
    params = {"W": jnp.ones((4, 4)), "b": jnp.ones((4,))}
    opt = adamw(lr=0.0, weight_decay=0.5)  # lr=0 isolates... decay scales by lr
    state = opt.init(params)
    updates, _ = opt.update({"W": jnp.zeros((4, 4)), "b": jnp.zeros((4,))},
                            state, params)
    # lr=0 => all updates zero; use lr>0 to see decay on W only
    opt = adamw(lr=0.1, weight_decay=0.5)
    state = opt.init(params)
    updates, _ = opt.update({"W": jnp.zeros((4, 4)), "b": jnp.zeros((4,))},
                            state, params)
    assert float(jnp.max(jnp.abs(updates["W"]))) > 0
    assert float(jnp.max(jnp.abs(updates["b"]))) == 0


def test_clip_by_global_norm():
    g = {"a": jnp.full((3,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(tree_norm(clipped)) - 1.0) < 1e-5
    assert abs(float(norm) - np.sqrt(300)) < 1e-3


def test_tree_dot_no_flatten():
    """tree_dot must not use vdot (sharding hazard) and must be exact."""
    a = {"x": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}
    assert abs(float(tree_dot(a, a)) - float(sum(i * i for i in range(6)))) < 1e-5


def test_schedules():
    s = cosine_schedule(1.0, warmup_steps=10, total_steps=100)
    assert float(s(0)) < 0.2            # warmup start
    assert abs(float(s(10)) - 1.0) < 0.1
    assert float(s(99)) < 0.2           # decayed
    w = linear_warmup(2.0, 4)
    assert abs(float(w(3)) - 2.0) < 1e-6


# ---------------------------------------------------------------------------
# HLO collective parser
# ---------------------------------------------------------------------------
HLO_SAMPLE = """
  %ar = bf16[1024,512] all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[64,256] all-gather(%y), replica_groups={{0,1},{2,3}}, dimensions={0}
  %rs = f32[128] reduce-scatter(%z), replica_groups={{0,1,2,3}}, to_apply=%add
  %aa = bf16[32,32] all-to-all(%w), replica_groups={{0,1,2,3}}
  %cp = s8[100] collective-permute(%v), source_target_pairs={{0,1}}
"""


def test_collective_parser_kinds_and_bytes():
    st = collective_bytes(HLO_SAMPLE)
    assert st.count_by_kind["all-reduce"] == 1
    assert st.count_by_kind["all-gather"] == 1
    assert abs(st.bytes_by_kind["all-reduce"] - 1024 * 512 * 2) < 1
    # all-gather payload = result / group_size (group 2)
    assert abs(st.bytes_by_kind["all-gather"] - 64 * 256 * 4 / 2) < 1
    assert st.total_bytes > 0


def test_shape_bytes_tuple():
    assert _shape_bytes("(bf16[8,8], f32[4])") == 8 * 8 * 2 + 4 * 4


def test_interpod_classifier():
    intra = "%a = f32[64] all-reduce(%x), replica_groups={{0,1,2,3}}"
    inter = "%a = f32[64] all-reduce(%x), replica_groups={{0,256},{1,257}}"
    st_i = collective_bytes(intra)
    st_x = collective_bytes(inter)
    assert st_i.inter_pod_bytes == 0
    assert st_x.inter_pod_bytes > 0


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
def test_synthetic_corpus_deterministic_and_learnable():
    a = SyntheticLMDataset(vocab_size=256, length=5000, seed=3)
    b = SyntheticLMDataset(vocab_size=256, length=5000, seed=3)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    # markov structure: bigram entropy well below unigram-uniform
    toks = a.tokens
    pairs = toks[:-1].astype(np.int64) * 256 + toks[1:]
    _, counts = np.unique(pairs, return_counts=True)
    p = counts / counts.sum()
    bigram_h = -(p * np.log(p)).sum()
    assert bigram_h < 2 * np.log(256) * 0.8


def test_batch_iterator_shapes():
    ds = SyntheticLMDataset(vocab_size=64, length=2000, seed=0)
    it = lm_batch_iterator(ds, batch=4, seq_len=16, seed=1)
    b = next(it)
    assert b["tokens"].shape == (4, 17)
    assert b["tokens"].dtype == np.int32


# ---------------------------------------------------------------------------
# sharding-safe compressor
# ---------------------------------------------------------------------------
def test_qsgd_sharded_no_flatten_and_bounded():
    c = qsgd_sharded(bits=8, block=8)
    assert c.flatten is False
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16)) * 5
    y = c(jax.random.PRNGKey(1), x)
    assert y.shape == x.shape
    # per-(row, block) absmax scale bounds the error
    xb = np.asarray(x).reshape(4, 2, 8)
    yb = np.asarray(y).reshape(4, 2, 8)
    scale = np.abs(xb).max(-1, keepdims=True) / 127
    assert (np.abs(yb - xb) <= scale + 1e-6).all()


def test_qsgd_sharded_odd_lastdim_fallback():
    c = qsgd_sharded(bits=8, block=8)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7))  # 7 % 8 != 0
    y = c(jax.random.PRNGKey(1), x)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir_is_fixed(monkeypatch, env_dir):
    from repro.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    try:
        where = compile_cache.use_compile_cache()
        if env_dir is None:
            # one fixed path inside the checkout, the same on every call
            assert where == str(compile_cache.DEFAULT_DIR)
            assert jax.config.jax_compilation_cache_dir == where
            assert compile_cache.use_compile_cache() == where
            assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
        else:
            # the environment wins and nothing is set in code
            assert where == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
