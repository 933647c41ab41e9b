"""Per-architecture smoke tests: reduced configs, one train step + prefill +
decode on CPU, asserting output shapes and finiteness (no NaNs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCHS, get_config, get_smoke_config, SHAPES, \
    supported_cells
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.models.layers import MeshCtx
from repro.train import optimizer as OPT


def _mcx():
    mesh = make_host_mesh()
    return MeshCtx(mesh=mesh, dp=("data",), tp="model")


def _batch(cfg, B=4, S=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    batch = {"labels": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)}
    if cfg.input_mode == "embeddings":
        batch["embeddings"] = jax.random.normal(ks[1], (B, S, cfg.d_model),
                                                jnp.float32)
    else:
        batch["tokens"] = jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    mcx = _mcx()
    mdl = M.build(cfg, mcx)
    params = mdl.init_params(jax.random.PRNGKey(0))
    opt = OPT.init_opt_state(params, mdl.opt_cfg)
    batch = _batch(cfg)
    new_p, new_o, metrics = jax.jit(mdl.train_step)(
        params, opt, batch, jnp.zeros((), jnp.int32))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    l0 = jax.tree.leaves(params)[0]
    l1 = jax.tree.leaves(new_p)[0]
    assert l0.shape == l1.shape
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_p)))
    assert changed


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if not get_config(a).is_encoder])
def test_smoke_prefill_decode(arch):
    cfg = get_smoke_config(arch)
    mcx = _mcx()
    mdl = M.build(cfg, mcx)
    params = mdl.init_params(jax.random.PRNGKey(1))
    B, S = 4, 32
    batch = {k: v for k, v in _batch(cfg, B, S).items() if k != "labels"}
    next_tok, caches = jax.jit(mdl.prefill_step)(params, batch)
    assert next_tok.shape == (B,)
    assert (np.asarray(next_tok) >= 0).all()
    assert (np.asarray(next_tok) < cfg.vocab_size).all()
    if cfg.input_mode == "embeddings":
        tok = jax.random.normal(jax.random.PRNGKey(2), (B, 1, cfg.d_model),
                                jnp.float32)
    else:
        tok = next_tok
    nt2, caches2 = jax.jit(mdl.decode_step)(
        params, caches, tok, jnp.array(S, jnp.int32))
    assert nt2.shape == (B,)
    assert (np.asarray(nt2) >= 0).all() and \
        (np.asarray(nt2) < cfg.vocab_size).all()
    # caches keep their structure
    assert jax.tree.structure(caches) == jax.tree.structure(caches2)


def test_decode_matches_forward_greedy():
    """Greedy continuation via decode == greedy via re-prefill (fp32)."""
    cfg = get_smoke_config("stablelm_12b").with_(dtype="float32")
    mcx = _mcx()
    mdl = M.build(cfg, mcx)
    params = mdl.init_params(jax.random.PRNGKey(3))
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0,
                                cfg.vocab_size)
    t1, caches = jax.jit(mdl.prefill_step)(params, {"tokens": tokens})
    # decode one token, then compare against prefill over the extended seq
    t2, _ = jax.jit(mdl.decode_step)(params, caches, t1,
                                     jnp.array(S, jnp.int32))
    ext = jnp.concatenate([tokens, t1[:, None]], axis=1)
    t2_ref, _ = jax.jit(mdl.prefill_step)(params, {"tokens": ext})
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(t2_ref))


def test_decode_matches_forward_ssm():
    cfg = get_smoke_config("falcon_mamba_7b").with_(dtype="float32")
    mcx = _mcx()
    mdl = M.build(cfg, mcx)
    params = mdl.init_params(jax.random.PRNGKey(5))
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0,
                                cfg.vocab_size)
    t1, caches = jax.jit(mdl.prefill_step)(params, {"tokens": tokens})
    t2, _ = jax.jit(mdl.decode_step)(params, caches, t1,
                                     jnp.array(S, jnp.int32))
    ext = jnp.concatenate([tokens, t1[:, None]], axis=1)
    t2_ref, _ = jax.jit(mdl.prefill_step)(params, {"tokens": ext})
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(t2_ref))


def test_param_counts_sane():
    """param_counts() roughly matches the advertised model size."""
    expect = {
        "falcon_mamba_7b": 7e9, "command_r_35b": 35e9,
        "nemotron_4_340b": 340e9, "stablelm_12b": 12e9,
        "starcoder2_15b": 15e9, "qwen3_moe_30b_a3b": 30e9,
        "deepseek_v3_671b": 671e9, "zamba2_1p2b": 1.2e9,
        "hubert_xlarge": 1e9, "internvl2_1b": 0.6e9,
    }
    for arch, target in expect.items():
        n = get_config(arch).param_counts()["total"]
        assert 0.4 * target < n < 2.1 * target, (arch, n, target)


def test_supported_cells_matrix():
    rows = {}
    for arch in ARCHS:
        cfg = get_config(arch)
        cells = supported_cells(cfg)
        rows[arch] = [s for s, (ok, _) in cells.items() if ok]
    assert "long_500k" in rows["falcon_mamba_7b"]
    assert "long_500k" in rows["zamba2_1p2b"]
    assert "long_500k" not in rows["command_r_35b"]
    assert "decode_32k" not in rows["hubert_xlarge"]
    total = sum(len(v) for v in rows.values())
    assert total == 31   # 40 cells - 7 long_500k skips - 2 hubert decode/long


@pytest.mark.parametrize("arch,flags", [
    ("stablelm_12b", {"flash_vjp": True, "explicit_tp": True}),
    ("qwen3_moe_30b_a3b", {"flash_vjp": True, "moe_dispatch": "a2a"}),
    ("deepseek_v3_671b", {"flash_vjp": True}),
])
def test_smoke_perf_variants(arch, flags):
    """The §Perf hillclimb paths stay numerically sane on CPU."""
    cfg = get_smoke_config(arch).with_(**flags)
    mcx = _mcx()
    mdl = M.build(cfg, mcx)
    params = mdl.init_params(jax.random.PRNGKey(0))
    opt = OPT.init_opt_state(params, mdl.opt_cfg)
    batch = _batch(cfg)
    _, _, metrics = jax.jit(mdl.train_step)(
        params, opt, batch, jnp.zeros((), jnp.int32))
    assert np.isfinite(float(metrics["loss"]))


def test_flash_vjp_matches_baseline_loss():
    cfg0 = get_smoke_config("stablelm_12b").with_(dtype="float32")
    cfg1 = cfg0.with_(flash_vjp=True)
    mcx = _mcx()
    m0, m1 = M.build(cfg0, mcx), M.build(cfg1, mcx)
    params = m0.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg0)
    l0, _ = m0.loss_fn(params, batch)
    l1, _ = m1.loss_fn(params, batch)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
