"""``chip_smoke.py`` on the CPU: its phases at small sizes, and its refusals.

The phases are the functions the chip run calls, seeded; here they run
the full-width VGG-16 at 2 clients, the REDUCED SmolLM and the grouped
experts at a small size.  ``main()``
itself insists on a TPU, which this machine does not have.
"""
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_phase_train_vgg_cpu():
    r = chip_smoke.phase_train_vgg(clients=2, edges=1, batch=2, rounds=4, seed=1)
    assert r["device"] == "cpu"
    assert len(r["losses"]) == 4 and np.all(np.isfinite(r["losses"]))
    assert r["compile_s"] > 0 and r["steady_s_per_round"] > 0


def test_phase_train_vgg_needs_a_fed_round():
    # three rounds of the default (8, 4, 1) plan never reach a fed sync
    with pytest.raises(ValueError, match="fed-sync round"):
        chip_smoke.phase_train_vgg(clients=2, edges=1, batch=2, rounds=3)


def test_phase_train_lm_reduced():
    r = chip_smoke.phase_train_lm(
        variant="reduced", num_layers=4, clients=2, edges=1, seq=16, batch=2,
        rounds=3, seed=1,
    )
    assert r["device"] == "cpu"
    assert len(r["losses"]) == 3 and np.all(np.isfinite(r["losses"]))
    assert len(set(r["losses"])) > 1


def test_phase_decode_reduced():
    from repro.configs import get_reduced

    spec = get_reduced("smollm-135m")
    r = chip_smoke.phase_decode(spec, batch=2, cache_len=16, prefill=3, gen=2)
    assert r["device"] == "cpu"
    assert r["logits_shape"] == [2, spec.padded_vocab]
    assert r["forward_rel_diff"] < 1e-4


def test_phase_grouped_experts_small():
    r = chip_smoke.phase_grouped_experts(tokens=32, top_k=3, held=4, d=128, f=128, seed=1)
    assert r["device"] == "cpu"
    assert 0 < r["pairs_held"] < r["pairs"]
    # off the chip the kernels are interpreted on float32 operands
    assert max(r["rel_diff"].values()) < 1e-5


def test_main_refuses_a_machine_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "src/repro" in out.stderr


SHARDED = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {root!r})
    sys.path.insert(0, os.path.join({root!r}, "src"))
    import chip_smoke
    r = chip_smoke.phase_sharded_vgg(clients=8, edges=2, batch=2, rounds=5)
    assert r["devices"] == [0, 1, 2, 3], r
    print("SHARDED-SMOKE-OK")
""")


def test_phase_sharded_vgg_on_four_cpu_devices():
    """The ``--four-chips`` phase rehearsed on four virtual CPU devices:
    8 clients in edge groups of 4 over shards of 2 take the one-hot
    einsum + psum path, and must match the single-device run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", SHARDED.format(root=str(ROOT))], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED-SMOKE-OK" in out.stdout


def test_compile_cache_env_wins(monkeypatch):
    import jax

    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.configure_compile_cache() == "/elsewhere/cache"
    assert calls == []


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    import jax

    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.configure_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert compile_cache.configure_compile_cache() == path
