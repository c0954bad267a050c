"""End-to-end driver tests: training loop (loss decreases, checkpoint
recovery works), serving loop (tokens come out), gradient compression
path, the compile-cache placement the entry points share, and
chip_smoke.py's refusal to run off a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _compile_cache_in_tmp(tmp_path, monkeypatch):
    """The entry points turn on JAX's persistent compilation cache; keep
    it in this test's tmp dir and turn it back off afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_train_driver_tiny(tmp_path):
    from repro.launch.train import main
    out = main([
        "--preset", "m100", "--steps", "25", "--batch", "4", "--seq", "64",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "10",
        "--log-every", "10",
    ])
    assert out["final_loss"] < out["first_loss"]


def test_train_driver_crash_recovery(tmp_path):
    from repro.launch.train import main
    out = main([
        "--preset", "m100", "--steps", "16", "--batch", "2", "--seq", "32",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "50",
        "--simulate-failure", "8", "--log-every", "8",
    ])
    assert out["steps"] >= 16  # re-ran the post-crash steps


def test_train_driver_compressed_grads(tmp_path):
    from repro.launch.train import main
    out = main([
        "--preset", "m100", "--steps", "20", "--batch", "4", "--seq", "32",
        "--ckpt-dir", str(tmp_path), "--compress-grads",
        "--log-every", "10",
    ])
    assert out["final_loss"] < out["first_loss"]


def test_serve_driver_decodes():
    from repro.launch.serve import main
    out = main(["--arch", "qwen3-1.7b", "--preset", "tiny", "--batch", "2",
                "--prompt-len", "16", "--gen", "8"])
    assert out["generated"].shape == (2, 8)
    assert out["tok_per_s"] > 0


def test_serve_driver_mla_absorb():
    """minicpm3-4b's tiny serve run decodes in the absorbed latent form,
    the one MLA decode path (no flag selects it)."""
    from repro.launch.serve import main
    out = main(["--arch", "minicpm3-4b", "--preset", "tiny", "--batch", "2",
                "--prompt-len", "16", "--gen", "4"])
    assert out["generated"].shape == (2, 4)
    assert out["tok_per_s"] > 0
    with pytest.raises(SystemExit):
        main(["--arch", "minicpm3-4b", "--mla-absorb"])


def test_serve_driver_ssm():
    from repro.launch.serve import main
    out = main(["--arch", "mamba2-130m", "--preset", "tiny", "--batch", "2",
                "--prompt-len", "16", "--gen", "4"])
    assert out["generated"].shape == (2, 4)


def test_compile_cache_dir_env_then_checkout(monkeypatch, tmp_path):
    from repro.launch.compile_cache import compile_cache_dir

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == str(ROOT / ".jax_cache")


def test_enable_compile_cache_points_jax_at_it(tmp_path):
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    assert enable_compile_cache() == str(tmp_path / "jc")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "jc")


def _smoke(script: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_cpu():
    out = _smoke(ROOT / "chip_smoke.py")
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_refuses(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _smoke(tmp_path / "chip_smoke.py")
    assert out.returncode != 0
    assert "no src/repro" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_phases_at_tiny_size():
    """The smoke's phases end to end on the CPU at tiny sizes: the same
    checks, with kernels on the XLA path instead of Mosaic."""
    sys.path.insert(0, str(ROOT))
    import chip_smoke

    chip_smoke.phase_serve("tiny", batch=2, prompt_len=16, gen=4)
    chip_smoke.phase_explorer(["radix_sort"])
    chip_smoke.phase_kernels(
        dict(b=2, hq=4, hkv=2, d=128, s=256, n_banks=4),
        dict(bt=1, h=4, q=64, p=32, n=16),
        dict(v=1024, d=256, n_banks=4, n=33), require_mosaic=False)


def test_chip_smoke_reference_prefill_keeps_the_scan(monkeypatch):
    """The serve phase's float32 reference runs its attention through
    the XLA scan, not through the prefill kernel it checks."""
    sys.path.insert(0, str(ROOT))
    import chip_smoke
    import jax.numpy as jnp

    from repro.configs import get_arch, tiny_variant
    from repro.models import attention

    def refuse(*a, **kw):
        raise AssertionError("the reference reached the prefill kernel")

    monkeypatch.setattr(attention, "flash_prefill", refuse)
    arch = tiny_variant(get_arch(chip_smoke.ARCH))
    tokens = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % arch.vocab
    logits = chip_smoke._reference_prefill(arch, tokens, 20)
    assert logits.shape == (2, arch.vocab)


def test_chip_smoke_sharded_phase_on_four_virtual_devices():
    """``--chips 4``'s path at tiny size on 4 virtual CPU devices: the
    2x2 mesh, the placement check and the one-device comparison.  The
    sharded prefill is traced under the mesh, so its attention takes the
    scan (a reused one-device trace would hold the kernel, which XLA
    cannot partition on the chip); the one-device prefill takes the
    kernel."""
    code = ("import chip_smoke\n"
            "from repro.launch.sharding import partitioned\n"
            "from repro.models import attention\n"
            "seen = []\n"
            "inner = attention.prefill_attention\n"
            "def spy(*a, **kw):\n"
            "    seen.append(partitioned())\n"
            "    return inner(*a, **kw)\n"
            "attention.prefill_attention = spy\n"
            "chip_smoke.phase_sharded('tiny', batch=8, prompt_len=16, "
            "steps=3)\n"
            "print('prefill_attention_partitioned', sorted(set(seen)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "[sharded.serve]" in out.stdout
    assert "prefill_attention_partitioned [False, True]" in out.stdout
