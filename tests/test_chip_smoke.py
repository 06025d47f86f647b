"""chip_smoke.py rehearsed on the CPU, so chip time is not spent on its
control flow: it must refuse to run off the chip, its phase functions
must run green at tiny size with the kernels interpreted, and the
compile-cache helper must place the cache where the contract says."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(args, **env_extra):
    """``python *args`` from the checkout, on the CPU, with no cache
    directory placed unless ``env_extra`` places one."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    return subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def test_refuses_to_run_off_the_chip():
    proc = _python(["chip_smoke.py"])
    assert proc.returncode != 0, proc.stdout
    assert "platform=cpu" in proc.stdout
    assert "found platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout          # no result line


def test_source_holds_the_chip_alone():
    """One process per chip: the smoke starts no child, sets no
    platform, and has no handler that lets a phase continue."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        src = f.read()
    for banned in ("subprocess", "multiprocessing", "JAX_PLATFORMS",
                   "jax_platforms", "except "):
        assert banned not in src, banned


def test_phases_run_green_at_tiny_size(devices):
    import chip_smoke
    from distributed_tensorflow_tpu.models.transformer import (
        TransformerConfig)

    kernels = dict(attention_impl="interpret", attn_block_q=64,
                   attn_block_k=64, loss_impl="kernel",
                   loss_kernel_impl="interpret", loss_block_n=32,
                   loss_block_v=64, remat=False, scan_layers=False,
                   adam_mu_dtype=jnp.bfloat16)
    cfg = TransformerConfig.tiny(**kernels)
    clock = chip_smoke.CompileClock()
    try:
        first = chip_smoke.train_phase(
            cfg, {"dp": 1}, devices[:1], steps=3, clock=clock,
            require_mosaic=False)
        chip_smoke.serve_phase(
            TransformerConfig.tiny(max_seq_len=64, scan_layers=False),
            chip_smoke.ServeShapes(
                num_blocks=96, block_size=8, max_slots=8,
                max_prompt_len=48, prompt_range=(4, 16),
                new_range=(4, 12), shared_len=40, suffix_range=(2, 6),
                n_requests=6),
            devices[0], clock=clock)
        # the looped shape, small: 2 layers x 3 passes, 6 cache layers
        import dataclasses
        looped = dataclasses.replace(
            chip_smoke.looped_config(), vocab_size=256, d_model=64,
            n_heads=4, d_ff=128, max_seq_len=64, passes=3,
            dtype=jnp.float32, param_dtype=jnp.float32)
        chip_smoke.serve_phase(
            looped, chip_smoke.ServeShapes(
                num_blocks=96, block_size=8, max_slots=8,
                max_prompt_len=48, prompt_range=(4, 16),
                new_range=(4, 12), shared_len=40, suffix_range=(2, 6),
                n_requests=6),
            devices[0], clock=clock)
        for axes in ({"dp": 4}, {"fsdp": 2, "tp": 2}):
            chip_smoke.train_phase(
                cfg, axes, devices[:4], steps=2, clock=clock,
                require_mosaic=False, one_chip_first_loss=first)
        # a wrong multi-chip loss is a failure, not a report
        with pytest.raises(chip_smoke.SmokeFailure, match="within 2e-2"):
            chip_smoke.train_phase(
                cfg, {"dp": 4}, devices[:4], steps=1, clock=clock,
                require_mosaic=False, one_chip_first_loss=first + 1.0)
    finally:
        clock.close()


# prints [dir before the helper, dir the helper returned, dir after]
_REPORT = ("import json, jax; "
           "from distributed_tensorflow_tpu.utils.compile_cache "
           "import enable_compile_cache; "
           "before = jax.config.jax_compilation_cache_dir; "
           "print(json.dumps([before, enable_compile_cache(), "
           "jax.config.jax_compilation_cache_dir]))")


def _report(**env_extra):
    proc = _python(["-c", _REPORT], **env_extra)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_helper_leaves_a_placed_cache_alone(tmp_path):
    placed = str(tmp_path / "placed")
    assert _report(JAX_COMPILATION_CACHE_DIR=placed) == [placed] * 3


def test_cache_helper_default_is_fixed_under_the_checkout():
    seen = _report()
    assert seen == _report()            # a second process agrees
    before, path, after = seen
    assert before is None and path == after
    assert path == os.path.join(REPO, ".cache", "dtx_jax_cache")
