"""The plain references against the program's forward passes, at a tiny
size on the CPU (float32 throughout there, so agreement is to float32
rounding)."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.reference import qwen2, vgg
from chipbench.tests import tiny


def test_vgg_reference_matches_the_programs_cnn():
    from repro.configs.base import CNNConfig, CNNLayer
    from repro.core.stages import CnnStageRunner
    cfg = tiny.VGG
    layers = tuple(CNNLayer("conv", out_ch=a[0]) if k == "conv" else
                   CNNLayer("pool", stride=2) if k == "pool" else
                   CNNLayer("dense", units=a[0]) if k == "dense" else
                   CNNLayer(k) for k, *a in cfg["layers"])
    pcfg = CNNConfig("t", "cnn", 32, 3, layers, 10)
    params = weights.cnn_params(cfg, 3)
    runner = CnnStageRunner(pcfg, params=params)
    x = weights.images(cfg, 3, 4)
    got = runner.stage_fn(0, runner.num_units)(params, {"image": x})["logits"]
    ref = vgg.logits(cfg, params, x, "highest")
    # float32 on the CPU both ways: only the order of the sums differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def _program_logits(cfg, params, tokens):
    """The program's own full forward (the slot pool's admission pass)."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.stateful import StatefulStageRunner
    pcfg = dataclasses.replace(
        get_config("qwen2.5-3b"), d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"], vocab_size=cfg["vocab_size"])
    runner = StatefulStageRunner(pcfg, params, max_seq=tokens.shape[1],
                                 decode_impl="reference")
    out = []
    for n in range(1, tokens.shape[1] + 1):
        lg, _, _ = runner.admit_fn()(params, jnp.asarray(tokens),
                                     jnp.int32(n))
        out.append(np.asarray(lg)[0])
    return np.stack(out)


def test_qwen2_reference_matches_the_programs_forward():
    cfg = tiny.QWEN
    params = weights.lm_params(cfg, 5)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                               (1, 12)).astype(np.int32)
    prog = _program_logits(cfg, params, tokens)
    h = qwen2.hidden(cfg, params, tokens)
    ref = np.asarray(jnp.matmul(h[0], params["embed"].T,
                                precision="highest"))
    # float32 on the CPU both ways; the chunked attention and the plain
    # softmax sum in different orders
    np.testing.assert_allclose(prog, ref, rtol=1e-4, atol=1e-4)


def test_gaps_are_zero_for_the_references_own_tokens():
    """Greedy tokens from the reference itself score a gap of 0, and a
    token that is not the best scores its distance below the best."""
    cfg = tiny.QWEN
    params = weights.lm_params(cfg, 5)
    seq = list(np.random.default_rng(1).integers(0, cfg["vocab_size"], 6))
    for _ in range(5):
        h = qwen2.hidden(cfg, params, np.asarray([seq], np.int32))
        lg = np.asarray(jnp.matmul(h[0, -1], params["embed"].T,
                                   precision="highest"))
        seq.append(int(lg.argmax()))
    seq = np.asarray(seq, np.int32)
    g = qwen2.gaps(cfg, params, [seq], [6], control=False)["served"][0]
    assert g.shape == (5,) and np.all(g <= 1e-6)
    bad = seq.copy()
    bad[-1] = (bad[-1] + 1) % cfg["vocab_size"]
    g2 = qwen2.gaps(cfg, params, [bad], [6], control=False)["served"][0]
    assert g2[-1] > 0 and np.allclose(g2[:-1], g[:-1])


def test_weights_per_seed():
    a = weights.lm_params(tiny.QWEN, 2 ** 33 + 1)
    b = weights.lm_params(tiny.QWEN, 2 ** 33 + 1)
    c = weights.lm_params(tiny.QWEN, 1)
    assert all(bool((x == y).all()) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool((a["embed"] == c["embed"]).all())
    assert a["layers"]["attn"]["wq"].shape == (4, 1024, 1024)
