"""Per-architecture smoke tests: a REDUCED same-family config runs one
forward + one train step on CPU, asserting shapes and finiteness.
The full configs are touched only by the dry-run (no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import registry as reg
from repro.models.registry import reduced_config
from repro.models.resnet_dcn import ResNetDCNConfig
from repro.optim import adamw, constant

ARCHS = reg.names()


@pytest.mark.parametrize("name", ARCHS)
def test_smoke_forward_and_train_step(name):
    arch = reg.get(name)
    cfg = reduced_config(arch)
    key = jax.random.PRNGKey(0)
    B, S = 2, 24

    if isinstance(cfg, ResNetDCNConfig):
        from repro.models import resnet_dcn as R
        from repro.data import DetectionDataConfig, detection_batch
        params = R.init_params(key, cfg)
        dcfg = DetectionDataConfig(img_size=cfg.img_size, global_batch=B,
                                   num_classes=cfg.num_classes)
        batch = {k: jnp.asarray(v) for k, v in
                 detection_batch(dcfg, 0).items()}
        out, o_maxes = R.forward(params, cfg, batch["images"])
        hc = cfg.img_size // 32
        assert out["cls"].shape == (B, hc, hc, cfg.num_classes + 1)
        assert out["box"].shape == (B, hc, hc, 4)
        assert len(o_maxes) == cfg.num_dcn
        lam = 0.005 if cfg.offset_bound is not None else 0.0
        (loss, m), grads = jax.value_and_grad(
            lambda p: R.train_loss(p, cfg, batch, lam=lam),
            has_aux=True)(params)
        assert np.isfinite(float(loss))
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in
                          jax.tree_util.tree_leaves(grads)))
        assert np.isfinite(float(gn))
        return

    from repro.models.transformer import init_params, loss_fn, forward
    params = init_params(key, cfg)
    tok_shape = (B, S) if cfg.codebooks == 1 else (B, S, cfg.codebooks)
    toks = jax.random.randint(key, tok_shape, 0, cfg.vocab)
    batch = {"tokens": toks, "targets": toks}
    if cfg.frontend_embeds:
        batch["frontend"] = jax.random.normal(
            key, (B, 4, cfg.d_model), jnp.float32)

    # forward shapes
    logits, _, _ = forward(params, cfg, tokens=toks,
                           frontend=batch.get("frontend"), mode="train")
    exp_s = S + (4 if cfg.frontend_embeds else 0)
    if cfg.codebooks > 1:
        assert logits.shape == (B, exp_s, cfg.codebooks, cfg.vocab)
    else:
        assert logits.shape == (B, exp_s, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    # one train step
    opt = adamw(constant(1e-3))
    state = opt.init(params)
    (loss, _), grads = jax.value_and_grad(
        lambda p: loss_fn(p, cfg, batch), has_aux=True)(params)
    assert np.isfinite(float(loss))
    new_params, _ = opt.update(grads, state, params, jnp.asarray(0))
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree_util.tree_leaves(new_params),
                                jax.tree_util.tree_leaves(params)))
    assert np.isfinite(delta) and delta > 0


@pytest.mark.parametrize("name", [n for n in ARCHS
                                  if reg.get(n).long_context_ok])
def test_long_context_archs_decode(name):
    """The two long_500k archs must decode at positions >> cache."""
    arch = reg.get(name)
    cfg = reduced_config(arch)
    from repro.models.transformer import (init_params, init_cache,
                                          decode_step)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, L = 2, 32
    caches = init_cache(cfg, B, L)
    pos = jnp.asarray([500, 700], jnp.int32)      # far beyond cache_len
    tok = jnp.zeros((B,), jnp.int32)
    logits, caches = decode_step(params, cfg, tok, caches, pos)
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_registry_cells_accounting():
    """40 LM cells = 32 runnable + 8 recorded skips; + 6 CNN cells (two
    shapes each of the three ResNet-DCN archs)."""
    cells = reg.runnable_cells()
    skips = reg.skipped_cells()
    lm = [c for c in cells if not c[0].startswith("resnet")]
    cnn = [c for c in cells if c[0].startswith("resnet")]
    assert len(lm) + len(skips) == 40
    assert len(cnn) == 6
    assert all(s[1] == "long_500k" for s in skips)
