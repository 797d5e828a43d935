"""Launch layer: mesh, input specs, roofline math, cell plumbing
(all device-free: AbstractMesh / pure functions)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, get_config
from repro.launch import roofline as rl
from repro.launch.cells import (input_specs, roofline_config,
                                slstm_flops_correction)


def test_input_specs_shapes():
    cfg = get_config("phi3-mini-3.8b")
    s = input_specs(cfg, SHAPES["train_4k"])
    assert s["tokens"].shape == (256, 4096)
    assert s["labels"].shape == (256, 4096)
    s = input_specs(cfg, SHAPES["decode_32k"])
    assert s["token"].shape == (128, 1)
    assert s["pos"].shape == (1,)


def test_input_specs_vlm_prefix():
    cfg = get_config("internvl2-76b")
    s = input_specs(cfg, SHAPES["train_4k"])
    # image patches replace the first frontend_seq backbone positions
    assert s["tokens"].shape == (256, 4096 - cfg.frontend_seq)
    assert s["frontend"].shape == (256, cfg.frontend_seq, cfg.d_model)


def test_input_specs_audio():
    cfg = get_config("whisper-medium")
    s = input_specs(cfg, SHAPES["prefill_32k"])
    assert s["frontend"].shape == (32, 1500, 1024)
    assert "labels" not in s


def test_roofline_config_depth_scaling():
    cfg = get_config("deepseek-67b")
    r1 = roofline_config(cfg, 1)
    r2 = roofline_config(cfg, 2)
    assert r1.n_layers == cfg.group_size
    assert r2.n_layers == 2 * cfg.group_size
    assert r1.scan_unroll and r1.attn_q_chunk > 1_000_000
    w = get_config("whisper-medium")
    assert roofline_config(w, 2).n_encoder_layers == 2


def test_slstm_correction_only_for_slstm():
    assert slstm_flops_correction(get_config("phi3-mini-3.8b"),
                                  SHAPES["train_4k"], 16) == 0
    x = slstm_flops_correction(get_config("xlstm-125m"),
                               SHAPES["train_4k"], 16)
    assert x > 0
    # decode: single step — nothing missing
    assert slstm_flops_correction(get_config("xlstm-125m"),
                                  SHAPES["decode_32k"], 16) == 0


def test_model_flops_conventions():
    cfg = get_config("mixtral-8x7b")
    tr = rl.model_flops_for(cfg, SHAPES["train_4k"])
    pf = rl.model_flops_for(cfg, SHAPES["prefill_32k"])
    dc = rl.model_flops_for(cfg, SHAPES["decode_32k"])
    n_act = cfg.active_param_count()
    assert tr == pytest.approx(6 * n_act * 256 * 4096)
    assert pf == pytest.approx(2 * n_act * 32 * 32768)
    assert dc == pytest.approx(2 * n_act * 128)
    # MoE: active < total
    assert cfg.active_param_count() < cfg.param_count()


def test_active_params_mixtral_magnitude():
    cfg = get_config("mixtral-8x7b")
    assert 40e9 < cfg.param_count() < 55e9       # ~47B total
    assert 10e9 < cfg.active_param_count() < 16e9  # ~13B active


def test_roofline_terms_and_bottleneck():
    colls = rl.CollectiveStats({"all-reduce": 2}, {"all-reduce": 10 ** 9},
                               cost_s=0.5)
    r = rl.Roofline(flops=197e12, hbm_bytes=819e9 / 4, collectives=colls,
                    n_chips=256, model_flops=197e12 * 256 * 0.5)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(0.25)
    assert r.bottleneck == "compute"
    assert r.step_s == pytest.approx(1.0)
    assert r.mfu == pytest.approx(0.5)


def test_peaks_table_keyed_by_device_kind():
    v5e = rl.peaks("TPU v5 lite")
    assert v5e.bf16_flops == 197e12 and v5e.hbm_bw == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        rl.peaks("cpu")


def test_shape_bytes_parser():
    assert rl._shape_bytes("bf16[16,128]{1,0}") == 16 * 128 * 2
    assert rl._shape_bytes("(f32[8]{0}, s32[4]{0})") == 8 * 4 + 4 * 4
    assert rl._shape_bytes("pred[10]") == 10


def test_make_production_mesh_requires_devices():
    # only 1 host device in the test process: building must fail loudly
    from repro.launch.mesh import make_production_mesh
    if len(jax.devices()) < 256:
        with pytest.raises(Exception):
            make_production_mesh()
