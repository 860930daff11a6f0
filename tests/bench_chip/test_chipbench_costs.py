"""FLOP, byte and peak arithmetic of the chip benchmark against hand
counts at the repo's smoke shapes (dense and state-space)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

from chipbench import costs  # noqa: E402

# phi3-medium-14b-smoke and mamba2-370m-smoke (src/repro/configs)
DENSE = {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
         "n_kv": 2, "head_dim": 16, "d_ff": 192, "vocab": 512,
         "tie_embeddings": False}
SSM = {"family": "ssm", "n_layers": 2, "d_model": 64, "n_heads": 4,
       "n_kv": 4, "head_dim": 32, "d_ff": 0, "vocab": 256,
       "tie_embeddings": True,
       "ssm": {"d_state": 16, "head_dim": 32, "expand": 2, "conv_width": 4,
               "chunk": 16, "n_groups": 1}}


def test_dense_counts_by_hand():
    # q 64x64, k and v 64x32, o 64x64, three 64x192 MLP maps
    assert costs.layer_linear_params(DENSE) == 8192 + 4096 + 36864
    # 2 layers x 2 x 49152 + 2 x 64 x 512 + 2 layers x 4 x 64 x 10
    assert costs.flops_per_token(DENSE, 10) == 196608 + 65536 + 5120
    # a 3-token prompt: 3 tokens' weights, attention over 1 + 2 + 3
    assert costs.prompt_flops(DENSE, 3) == 3 * (196608 + 65536) \
        + 2 * 4 * 64 * 6
    # weights 98304 + head 32768 + 8 embedding rows + norms 2x2x64 + 64
    assert costs.base_weight_bytes(DENSE, 8) == 2 * (98304 + 32768 + 512
                                                     + 256 + 64)
    # K and V of 10 positions, 2 kv heads x 16, 2 bytes, 2 layers
    assert costs.cache_bytes(DENSE, 10) == 2 * 2 * 2 * 10 * 32


def test_ssm_counts_by_hand():
    # z, x 64x128; B and C 64x32; dt 64x4; out 128x64
    assert costs.layer_linear_params(SSM) == 64 * (256 + 32 + 4) + 8192
    # heads 4 x 32 x 16 state: 6 per entry; conv 4 taps x (128 + 32)
    mixer = 6 * 4 * 32 * 16 + 2 * 4 * 160
    assert costs.flops_per_token(SSM, 999) == 2 * 2 * 26880 + 2 * 64 * 256 \
        + 2 * mixer
    assert costs.prompt_flops(SSM, 5) == 5 * costs.flops_per_token(SSM, 0)
    # tied: the whole table is the head; vectors 64 + 128 + 5 x 160 + 12
    assert costs.base_weight_bytes(SSM, 8) == 2 * (2 * 26880 + 2 * 1004
                                                   + 64 * 256 + 64)
    # f32 state read and written, bf16 conv rings read and written
    assert costs.cache_bytes(SSM, 1) == 2 * 2 * (4 * 2048 + 2 * 3 * 160)


def test_linear_params_match_the_served_layout():
    """The hand formulas agree with the program's own parameter shapes."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.utils.pytree import flatten_with_paths
    for name, a, leaves in (
            ("phi3-medium-14b", DENSE, ("attn/wq", "attn/wk", "attn/wv",
                                        "attn/wo", "mlp/wi", "mlp/wg",
                                        "mlp/wo")),
            ("mamba2-370m", SSM, ("ssm/wz", "ssm/wx", "ssm/wbc", "ssm/wdt",
                                  "ssm/wout"))):
        cfg = get_smoke_config(name)
        flat = flatten_with_paths(lm.param_specs(cfg))
        n = sum(flat[p].shape[-2] * flat[p].shape[-1] for p in leaves)
        assert n == costs.layer_linear_params(a)


def test_packed_bytes():
    t16 = {"alpha": 8.0, "k_bits": 8, "m": 1, "h_g": 16}
    t128 = {"alpha": 8.0, "k_bits": 4, "m": 8, "h_g": 16}
    # 2 kept of 16: 2 index bytes + 2 code bytes (8-bit) or 1 (4-bit)
    assert costs.packed_leaf_bytes(64, 32, 2, t16) == 2 * (4 * 32 * 4 + 8)
    assert costs.packed_leaf_bytes(64, 32, 2, t128) == 2 * (4 * 32 * 3 + 8)


def test_peaks_table():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        costs.peaks("cpu")
