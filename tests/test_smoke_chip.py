"""chip_smoke.py off the chip: its phase functions pass on the CPU at tiny
sizes with interpret=True, and the script itself refuses to run there."""
import dataclasses
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

TINY = dataclasses.replace(
    chip_smoke.FULL,
    flash_shapes=((2, 24, 8, "bfloat16", True), (2, 20, 8, "float32", False)),
    bn_shapes=((16, 8, "bfloat16"), (8, 12, "float32")),
    lstm_shapes=((5, 3, 4, 6),),
    resnet={"blocks": (1,), "width": 8}, image=16, n_classes=4,
    train_batch=8, fit_steps=3, scan_steps=2,
    vocab=64, width=32, heads=4, blocks=2, context=32, lm_batch=4,
    lm_steps=3, prompt_len=12, gen_tokens=3, check_ticks=3,
    rnn_vocab=12, rnn_hidden=8, rnn_batch=4, rnn_seq=8, rnn_tbptt=4)


@pytest.mark.parametrize("phase", ["kernels", "train", "lm", "char_rnn",
                                   "mesh"])
def test_phase_passes_on_cpu_at_tiny_size(phase):
    out = getattr(chip_smoke, f"phase_{phase}")(TINY, True)
    assert out


def test_script_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stdout[-2000:] + p.stderr[-2000:]
    assert "no TPU chip" in p.stderr
    assert '"ok"' not in p.stdout


def test_result_line_has_exactly_the_contract_keys():
    import json
    for ok in (True, False):
        rec = json.loads(chip_smoke.result_line(ok))
        assert set(rec) == {"ok", "device"} and rec["ok"] is ok
        dev = rec["device"]
        assert set(dev) == {"platform", "kind", "count"}
        assert isinstance(dev["platform"], str) and isinstance(dev["kind"], str)
        assert type(dev["count"]) is int


def test_mosaic_kernels_reads_names_from_lowered_text():
    text = ('stablehlo.custom_call @tpu_custom_call(%0) {kernel_name = '
            '"flash_fwd"} ... @tpu_custom_call(%1) {kernel_name = "lstm_bwd"}')
    assert chip_smoke.mosaic_kernels(text) == ["flash_fwd", "lstm_bwd"]
    assert chip_smoke.mosaic_kernels('kernel_name = "x"') == []


# -- the compile cache can be placed from outside ---------------------------
@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them: the test
    process must not start writing a persistent cache."""
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def test_cache_dir_from_environment_sets_nothing(monkeypatch, tmp_path,
                                                 config_updates):
    from deeplearning4j_tpu.util import platform
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    assert platform.enable_compilation_cache() == str(tmp_path / "outside")
    assert not [k for k, _ in config_updates if "cache_dir" in k]


def test_cache_dir_defaults_to_fixed_path_in_checkout(monkeypatch,
                                                      config_updates):
    from deeplearning4j_tpu.util import platform
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert platform.COMPILE_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    assert platform.enable_compilation_cache() == platform.COMPILE_CACHE_DIR
    assert [v for k, v in config_updates if "cache_dir" in k] == [
        platform.COMPILE_CACHE_DIR]


def test_cache_that_cannot_be_enabled_raises(monkeypatch, tmp_path,
                                             config_updates):
    from deeplearning4j_tpu.util import platform
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    blocker = tmp_path / "file"
    blocker.write_text("")
    monkeypatch.setattr(platform, "COMPILE_CACHE_DIR",
                        str(blocker / "cache"))
    with pytest.raises(OSError):
        platform.enable_compilation_cache()
    assert not config_updates
