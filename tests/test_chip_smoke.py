"""``chip_smoke.py`` on the CPU: its refusals, and its phases at a tiny size.

The script itself refuses to run anywhere but on a TPU, so these tests
call its phase functions directly (that is the steering: no option of
the script selects the CPU) at the smoke config's widths.
"""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

SMOKE_SERVE_ARGS = ["--arch", "qwen3-8b", "--kernel", "--slots", "4",
                    "--cache-len", "256", "--chunk", "16", "--page-size",
                    "16", "--seed", "0"]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd, script, *args, env=None):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    for args in ((), ("--four-chips",)):
        r = _run(ROOT, SCRIPT, *args)
        assert r.returncode != 0
        assert '"ok"' not in r.stdout
        assert "needs" in r.stderr and "TPU" in r.stderr


def test_refuses_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(tmp_path, tmp_path / "chip_smoke.py")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_serve_and_kernel_phases_at_smoke_size():
    cs = _load()
    cfg, engine = cs.serve_phase(SMOKE_SERVE_ARGS, max_new=4)
    assert engine.use_kernel and engine.paged and engine._can_share
    assert engine.stats["shared_pages"] and engine.stats["cow_copies"] == 2
    # interpret mode on the CPU: the program holds no Mosaic call
    assert cs.kernel_phase(cfg, engine, seed=1) is False


def test_prompts_share_a_prefix_and_repeat_in_whole_chunks():
    cs = _load()
    prompts = cs.make_prompts(chunk=16, vocab=512, seed=0)
    assert len(prompts) == cs.N_REQUESTS
    assert prompts[2] == prompts[0] and prompts[3] == prompts[1]
    assert all(p[:32] == prompts[0][:32] for p in prompts)
    assert all(len(p) % 16 == 0 for p in prompts)


PIPELINE = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util, jax
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cs.pipeline_phase(jax.devices(), d=256, batch=8)
print("PIPELINE_PHASE_OK")
"""


def test_pipeline_phase_on_four_host_devices():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", PIPELINE, str(SCRIPT)],
                       env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert "PIPELINE_PHASE_OK" in r.stdout, r.stderr[-2000:]
    assert "stage 3 on TFRT_CPU_3" in r.stdout


def test_chip_flag_selects_published_widths():
    from repro.configs import get_config
    from repro.launch import serve

    full = get_config("qwen3-8b")
    smoke = serve.model_config(serve.build_parser().parse_args([]))
    chip = serve.model_config(serve.build_parser().parse_args(["--chip"]))
    assert smoke.d_model < full.d_model
    assert chip.n_layers == 18 and full.n_layers == 36
    for f in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "qk_norm", "rope_theta"):
        assert getattr(chip, f) == getattr(full, f), f
    with pytest.raises(ValueError, match="no chip-share preset"):
        serve.model_config(serve.build_parser().parse_args(
            ["--chip", "--arch", "gpt3-24l"]))


def test_compile_cache_dir(monkeypatch):
    import jax
    from repro.launch.compile_cache import use_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(ROOT / ".jax_cache")
        assert use_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
