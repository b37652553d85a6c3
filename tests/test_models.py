"""Model zoo: init/forward shapes, grad steps, sharded embedding tables."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from edl_tpu.models import (
    BowClassifier, CnnClassifier, LinearRegression, MnistCNN, ResNet18,
    ResNet50, ResNet50vd, TextTransformer, TransformerConfig, TransformerLM,
    VGG16, WideDeep, logical_axes_from_paths,
)
from edl_tpu.models.transformer import LOGICAL_RULES, lm_loss
from edl_tpu.models import wide_deep as wd_mod
from edl_tpu.parallel import MeshSpec, ShardingRules
from edl_tpu.train import ElasticTrainer, TrainConfig

KEY = jax.random.key(0)


def test_linear_forward():
    m = LinearRegression()
    params = m.init(KEY, jnp.ones((2, 13)))
    out = m.apply(params, jnp.ones((2, 13)))
    assert out.shape == (2, 1)


def test_mnist_cnn_forward():
    m = MnistCNN()
    x = jnp.ones((2, 28, 28, 1))
    params = m.init(KEY, x)
    assert m.apply(params, x).shape == (2, 10)


@pytest.mark.parametrize("ctor,extra_stem", [(ResNet18, False),
                                             (ResNet50, False),
                                             (ResNet50vd, True)])
def test_resnet_forward(ctor, extra_stem):
    m = ctor(num_classes=10)
    x = jnp.ones((1, 32, 32, 3))
    variables = m.init(KEY, x, train=False)
    out = m.apply(variables, x, train=False)
    assert out.shape == (1, 10)
    assert out.dtype == jnp.float32
    assert ("stem1" in variables["params"]) == extra_stem
    # train mode returns updated batch stats
    out, mutated = m.apply(variables, x, train=True, mutable=["batch_stats"])
    assert "batch_stats" in mutated


def test_vgg_forward():
    m = VGG16(num_classes=7)
    x = jnp.ones((1, 32, 32, 3))
    variables = m.init(KEY, x, train=False)
    assert m.apply(variables, x, train=False).shape == (1, 7)


def test_text_models_forward():
    ids = jnp.ones((2, 16), jnp.int32)
    mask = jnp.ones((2, 16))
    for m in (BowClassifier(vocab_size=100), CnnClassifier(vocab_size=100),
              TextTransformer(vocab_size=100, num_layers=2, embed_dim=32,
                              num_heads=2, mlp_dim=64, max_len=32)):
        params = m.init(KEY, ids, mask)
        assert m.apply(params, ids, mask).shape == (2, 2)


def test_wide_deep_sharded_tables():
    mesh_spec = MeshSpec(dp=2, ep=4)
    model = WideDeep(vocab_sizes=(1000, 1000, 500), dense_features=4,
                     embed_dim=8, hidden=(16,))
    dense = np.ones((8, 4), np.float32)
    sparse = np.zeros((8, 3), np.int64)

    def loss_fn(params, extra, batch, rng):
        logit = model.apply({"params": params}, batch["dense"], batch["sparse"])
        labels = batch["y"]
        l = optax.sigmoid_binary_cross_entropy(logit, labels).mean()
        return l, (extra, {})

    tr = ElasticTrainer(loss_fn, TrainConfig(mesh_spec=mesh_spec, log_every=0))

    def init():
        v = model.init(KEY, jnp.asarray(dense), jnp.asarray(sparse))
        return v["params"], None

    logical = lambda params: logical_axes_from_paths(params, wd_mod.LOGICAL_RULES)
    params_shape = jax.eval_shape(lambda: init()[0])
    state = tr.create_state(init, optax.adam(1e-3),
                            param_logical=logical(params_shape))
    # embedding tables sharded over ep on the vocab dim
    assert state.params["embed_0"]["embedding"].sharding.spec[0] == "ep"
    from edl_tpu.parallel.sharding import shard_host_batch
    batch = shard_host_batch({"dense": dense, "sparse": sparse,
                              "y": np.ones((8,), np.float32)}, tr.mesh)
    state2, metrics = tr.step_fn(state, batch, KEY)
    assert np.isfinite(float(metrics["loss"]))


def test_transformer_lm_trains_and_rules_cover_params():
    cfg = TransformerConfig(vocab_size=128, num_layers=2, embed_dim=64,
                            num_heads=4, mlp_dim=128, max_len=32,
                            dtype=jnp.float32, attention_impl="dense",
                            remat=False)
    model = TransformerLM(cfg)
    ids = jax.random.randint(KEY, (2, 16), 0, 128)
    variables = model.init(KEY, ids)
    logits = model.apply(variables, ids)
    assert logits.shape == (2, 16, 128)

    # scanned layers: params have a leading layers dim
    qkv = variables["params"]["layers"]["attn_qkv"]["kernel"]
    assert qkv.shape[0] == 2

    logical = logical_axes_from_paths(variables["params"], LOGICAL_RULES)
    flat = jax.tree.leaves(logical, is_leaf=lambda x: isinstance(x, tuple))
    assert all(isinstance(t, tuple) for t in flat)

    # a couple of SGD steps reduce loss
    params = variables["params"]
    tx = optax.adam(1e-2)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt):
        def lf(p):
            logits = model.apply({"params": p}, ids[:, :-1])
            return lm_loss(logits, ids[:, 1:])
        loss, grads = jax.value_and_grad(lf)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    params, opt, l0 = step(params, opt)
    for _ in range(5):
        params, opt, l = step(params, opt)
    assert float(l) < float(l0)


def test_transformer_tp_sharding_end_to_end():
    """TP+DP mesh: logits match the single-device model."""
    cfg = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=16,
                            dtype=jnp.float32, attention_impl="dense",
                            remat=False)
    model = TransformerLM(cfg)
    ids = jax.random.randint(KEY, (4, 16), 0, 64)
    variables = model.init(KEY, ids)
    expected = model.apply(variables, ids)

    from edl_tpu.parallel import build_mesh, logical_sharding
    from edl_tpu.parallel.sharding import ShardingRules, shard_host_batch
    mesh = build_mesh(MeshSpec(dp=2, tp=4))
    rules = ShardingRules()
    logical = logical_axes_from_paths(variables["params"], LOGICAL_RULES)
    params = jax.tree.map(
        lambda x, ax: jax.device_put(x, logical_sharding(ax, mesh, rules)),
        variables["params"], logical)
    gids = shard_host_batch({"ids": np.asarray(ids)}, mesh, rules)["ids"]
    out = jax.jit(lambda p, i: model.apply({"params": p}, i))(params, gids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-3, atol=2e-3)


def test_auto_layout_decisions():
    """The shipped defaults must BE the fast configuration: unroll
    shallow stacks, remat only when the batch misses HBM (calibrated on
    measured v5e runs — the flagship trains un-remat'd at bs 8 and OOMs
    at bs 16 on 16 GB)."""
    from edl_tpu.models.transformer import TransformerConfig, auto_layout

    flag = TransformerConfig()          # 12L x 768, seq 1024
    bs8 = auto_layout(flag, 8, 1024, hbm_bytes=16.6e9)
    assert bs8.remat is False and bs8.scan_layers is False
    bs16 = auto_layout(flag, 16, 1024, hbm_bytes=16.6e9)
    assert bs16.remat is True
    deep = auto_layout(TransformerConfig(num_layers=48), 8, 1024,
                       hbm_bytes=16.6e9)
    assert deep.scan_layers is True and deep.remat is True


# the widths of the benchmark's two train configurations (benchmarks/
# configs/): name, layers, embed, heads, kv heads, mlp, rows a chip
_TRAIN_WIDTHS = {
    "mistral-7b-d2": (2, 4096, 32, 8, 14336, 4),
    "codestral-22b-d4": (4, 6144, 48, 8, 16384, 1),
    "mistral-7b-d32": (32, 4096, 32, 8, 14336, 4),
}


def _train_cfg(name, **kw):
    from edl_tpu.models.transformer import TransformerConfig
    layers, embed, heads, kv, mlp, rows = _TRAIN_WIDTHS[name]
    return TransformerConfig(
        vocab_size=32768, num_layers=layers, embed_dim=embed,
        num_heads=heads, num_kv_heads=kv, mlp_dim=mlp, max_len=4096,
        **kw), rows


def test_auto_layout_counts_what_attention_keeps():
    """``_remat`` keeps the splash forward's ``out`` and logsumexp for
    the backward pass; the estimate ``auto_layout`` decides from holds
    them: 136 MB a layer at 4 x 4096 tokens of 32 heads of 128, 4.3 GB
    at depth 32, on top of what it counted before."""
    from edl_tpu.models.transformer import (
        _ACT_VALS_PER_TOK_LAYER_EMBED, attention_backward_bytes,
        attention_kept_bytes, param_count, train_bytes_estimate)
    cfg, rows = _train_cfg("mistral-7b-d32")
    before = (16 * param_count(cfg)
              + 2 * rows * 4096 * 32 * 4096 * _ACT_VALS_PER_TOK_LAYER_EMBED
              + 2 * 4 * rows * 4096 * 32768)
    kept = attention_kept_bytes(cfg, rows, 4096)
    assert kept == 32 * (4 * 4096 * 32 * 128 * 2 + 4 * 32 * 4096 * 4)
    assert 4.3e9 < kept < 4.4e9
    # and, once for the stack, the four partial dQs the fused backward
    # of one layer writes (537 MB here)
    partials = attention_backward_bytes(cfg, rows, 4096)
    assert partials == 4 * (4 * 4096 * 32 * 128 * 2)
    assert train_bytes_estimate(cfg, rows, 4096) - before == kept + partials


def test_auto_layout_counts_attention_layers_only():
    """A state-space layer runs no attention kernel and keeps nothing
    of one."""
    import dataclasses

    from edl_tpu.models.transformer import attention_kept_bytes
    cfg, rows = _train_cfg("mistral-7b-d2")
    mixed = dataclasses.replace(cfg, layer_attn=("ssm", "global"),
                                ssm_heads=64)
    assert (2 * attention_kept_bytes(mixed, rows, 4096)
            == attention_kept_bytes(cfg, rows, 4096))


@pytest.mark.parametrize("stack,seq,partials", [
    (dict(), 4096, 4), (dict(), 8192, 4), (dict(), 1024, 2),
    (dict(), 2048, 0), (dict(attn_window=128), 4096, 0),
    (dict(attn_window=128, layer_attn=("window", "global")), 4096, 4),
    (dict(layer_attn=("ssm", "ssm"), ssm_heads=64), 4096, 0)],
    ids=["4096", "8192", "1024", "unswept", "window", "non-uniform", "ssm"])
def test_auto_layout_counts_the_fused_backwards_partials(stack, seq,
                                                         partials):
    """The buffer is there exactly where ``splash_block_sizes`` takes
    the fused backward (a swept length, no window), for the one layer
    whose backward is running."""
    import dataclasses

    from edl_tpu.models.transformer import attention_backward_bytes
    cfg, rows = _train_cfg("mistral-7b-d2")
    cfg = dataclasses.replace(cfg, max_len=seq, **stack)
    assert (attention_backward_bytes(cfg, rows, seq)
            == partials * rows * seq * 32 * 128 * 2)


@pytest.mark.parametrize("name", ["mistral-7b-d2", "codestral-22b-d4"])
def test_auto_layout_keeps_remat_on_for_the_train_cells(name):
    """The two train configurations' shapes decide as they did: remat
    on (the state alone is most of the chip), layers unrolled."""
    from edl_tpu.models.transformer import auto_layout
    cfg, rows = _train_cfg(name)
    got = auto_layout(cfg, rows, 4096, hbm_bytes=16.9e9)
    assert got.remat is True and got.scan_layers is False
