"""CPU rehearsal of ``chip_smoke.py`` and of the rules it leans on.

The script itself has no way onto the CPU (it fails at its device check), so
its phases are plain functions of ``(ctx, rows, seed)`` and are called here
at a tiny size on the virtual CPU mesh, each against its own reference. A
pass here says the control flow and the references are right; only the
script's run on the chip says the path works there.
"""
import os

import jax
import pytest

import chip_smoke  # importing it touches no device
import cylon_tpu as ct
from cylon_tpu import context as _context


@pytest.mark.parametrize(
    "phase,world",
    [
        ("wide_phase", 1),
        ("default_dtype_phase", 1),
        ("cross_chip_phase", 4),
        ("default_dtype_phase", 4),
    ],
)
def test_phase_matches_reference_on_cpu_mesh(devices, phase, world):
    ctx = ct.CylonContext.init_distributed(ct.TPUConfig(devices=devices[:world]))
    obs = getattr(chip_smoke, phase)(ctx, 4096, 0)
    assert obs["world"] == world and obs["rows_a_side"] == 4096
    assert obs["join_rows"] > 0 and obs["groups"] > 0
    assert obs["sorted_rows"] == 4096
    if world > 1:
        assert len(obs["join_rows_per_shard"]) == world


def test_main_exits_nonzero_on_cpu_before_any_phase(monkeypatch, capsys):
    def ran(*_a, **_k):
        raise AssertionError("a phase ran without a TPU")

    for name in ("wide_phase", "default_dtype_phase", "cross_chip_phase"):
        monkeypatch.setattr(chip_smoke, name, ran)
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert capsys.readouterr().out == ""  # no result line


@pytest.mark.parametrize(
    "platform,env_dir,opt_out,want",
    [
        ("tpu", None, "", _context.DEFAULT_COMPILE_CACHE),
        ("tpu", "/somewhere/else", "", None),  # jax already caches there
        ("tpu", None, "0", None),
        ("cpu", None, "", None),
    ],
)
def test_one_compile_cache(monkeypatch, platform, env_dir, opt_out, want):
    """JAX_COMPILATION_CACHE_DIR places the cache and then nothing is set
    in code; unset, an accelerator context caches in <checkout>/.jax_cache."""
    updates = {}
    monkeypatch.setattr(_context, "_compile_cache_set", False)
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    monkeypatch.setenv("CYLON_TPU_COMPILE_CACHE", opt_out)
    _context._enable_compile_cache(platform)
    assert updates.get("jax_compilation_cache_dir") == want
    if want is None:
        assert updates == {}
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert want == os.path.join(repo, ".jax_cache")
