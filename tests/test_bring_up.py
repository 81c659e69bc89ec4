"""PR 23 bring-up guards: where the compile cache goes, that an unknown TPU
kind is an error, that measurement entry points refuse the CPU, and that
``chip_smoke.py``'s parent stays off JAX (one process per chip)."""

import os
import pathlib
import subprocess
import sys
import types

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _cache_dir_after_configure(env_value):
    """Run compile_cache.configure() in a fresh process; report what the
    helper returned and what JAX's config then holds."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run(
        [sys.executable, "-c",
         "from avenir_tpu.utils import compile_cache as c\n"
         "import sys\n"
         "r = c.configure()\n"
         "jax_loaded = 'jax' in sys.modules\n"
         "import jax\n"
         "print(repr(r)); print(jax_loaded)\n"
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, check=True, cwd="/")
    returned, jax_loaded, configured = out.stdout.strip().splitlines()
    return returned, jax_loaded == "True", configured


@pytest.mark.parametrize("env_value", [None, "/somewhere/else"],
                         ids=["unset", "env-set"])
def test_compile_cache_placement(env_value):
    returned, jax_loaded, configured = _cache_dir_after_configure(env_value)
    if env_value is None:
        # ONE fixed path inside the checkout, whatever the cwd
        assert returned == repr(str(REPO / ".jax_cache"))
        assert configured == str(REPO / ".jax_cache")
    else:
        # the environment placed it: code sets nothing (does not even
        # import jax), and JAX itself reads the variable
        assert returned == "None" and not jax_loaded
        assert configured == env_value


def test_chip_peaks_raises_on_unknown_tpu_kind(monkeypatch):
    import jax

    from avenir_tpu.utils import roofline

    def fake(kind, platform="tpu"):
        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])

    fake("TPU v5 lite")                      # what a v5e reports
    assert roofline.chip_peaks()["int8_ops"] == 394e12
    fake("TPU v9 hyper")
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        roofline.chip_peaks()
    fake("cpu", platform="cpu")              # tests: zeros, never a guess
    assert roofline.chip_peaks()["bf16_flops"] == 0.0


def test_measurement_entry_points_refuse_the_cpu(monkeypatch):
    """bench.py and benchmarks/multichip_scan.py print device metrics:
    their main() raises where JAX finds no TPU (the import stays free —
    tests/test_benchmarks_import.py imports every bench module).  The
    ``--nprocs`` worker refuses too, once it has joined its fleet."""
    import bench
    from avenir_tpu import launch
    from benchmarks import multichip_scan

    with pytest.raises(RuntimeError, match="measures the TPU"):
        bench.main()
    with pytest.raises(RuntimeError, match="measures the TPU"):
        multichip_scan._single_process_main()
    monkeypatch.setattr(launch, "join_from_env", lambda: 0)
    with pytest.raises(RuntimeError, match="measures the TPU"):
        multichip_scan._multiproc_worker(types.SimpleNamespace(out=None))


def test_chip_smoke_parent_imports_no_jax():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke\n"
         "bad = sorted(m for m in sys.modules if m == 'jax' or "
         "m.startswith(('jax.', 'jaxlib', 'avenir_tpu')))\n"
         "print(bad)"],
        env=dict(os.environ, PYTHONPATH=str(REPO)), cwd=str(REPO),
        capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "[]"
    # and nothing in the script lets a child run anywhere but on the TPU
    assert 'env["JAX_PLATFORMS"] = "tpu"' in (REPO / "chip_smoke.py"
                                              ).read_text()
