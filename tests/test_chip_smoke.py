"""CPU rehearsal of `chip_smoke.py`: its phase functions at the `smoke`
preset, with the same reference checks the chip run makes at `base`."""
import importlib
import inspect
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

SMALL = dict(n_batches=2, batch=2048, n_nodes=3000)


@pytest.mark.parametrize(
    "backends",
    [{}, {"ingest_backend": "pallas", "query_backend": "pallas"}],
    ids=["auto", "pallas-interpret"],
)
def test_session_phase_matches_references(backends):
    report = chip_smoke.phase_session("smoke", **SMALL, **backends)
    assert report["edges"] == 2 * SMALL["n_batches"] * SMALL["batch"]
    if backends:
        assert report["ingest_backend"] == report["query_backend"] == "pallas"


def test_reference_checks_catch_a_wrong_answer():
    from repro.api import GraphStream

    src, dst, w, rng = chip_smoke.make_stream(1, 1024, 500)
    gs = GraphStream.open("smoke")
    ref = chip_smoke.Reference(gs.sketch)
    gs.ingest(src, dst, w)
    ref.add(src, dst, w)
    queries, qs, qd = chip_smoke.workload(src, dst, rng, 500)
    results = gs.query(queries)
    chip_smoke.check_answers("exact", results, ref, qs, qd, gs.sketch)
    ref.add(src[:1], dst[:1], w[:1])  # one edge the session never saw
    with pytest.raises(chip_smoke.SmokeFailure, match="edge answers"):
        chip_smoke.check_answers("off by one edge", results, ref, qs, qd)


def test_serve_phase_replays_its_wal(tmp_path):
    report = chip_smoke.phase_serve(
        tmp_path / "wal", width=256, depth=3, edges=4096, batch=1024
    )
    assert report["edges"] == 4096
    assert report["checked"].startswith("WAL replays 4 batches")


def test_fleet_phase():
    report = chip_smoke.phase_fleet(tenants=8, width=128, depth=3, edges=4096, batch=1024)
    assert report["edges"] == 4096


@pytest.mark.slow
def test_mesh_phase_on_four_cpu_devices():
    script = textwrap.dedent(
        """
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, ".")
        import chip_smoke
        for shape in ((2, 2), (1, 4)):
            print(chip_smoke.phase_mesh(
                "smoke", shape=shape, n_batches=2, batch=2048, n_nodes=3000
            ))
        """
    )
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "counters on 4 devices, 128 rows each; bit-identical; 2 stream shards" in proc.stdout
    assert "counters on 4 devices, 64 rows each; bit-identical; update: no collective" in proc.stdout


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300,
    )
    assert proc.returncode != 0 and proc.stdout == ""
    assert "not a checkout" in proc.stderr


def test_no_kernel_wrapper_defaults_to_the_interpreter():
    wrappers = 0
    for path in sorted((ROOT / "src" / "repro" / "kernels").glob("*/*.py")):
        if path.stem not in ("kernel", "ops"):
            continue
        mod_name = f"repro.kernels.{path.parent.name}.{path.stem}"
        module = importlib.import_module(mod_name)
        for name, fn in vars(module).items():
            if not callable(fn) or getattr(fn, "__module__", None) != mod_name:
                continue
            param = inspect.signature(fn).parameters.get("interpret")
            if param is not None:
                wrappers += 1
                assert param.default is None, f"{mod_name}.{name} defaults interpret"
    assert wrappers >= 15


def test_compile_cache_dir(monkeypatch, tmp_path):
    import jax

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None  # JAX reads the env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(ROOT / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_serve_writes_its_compile_cache_where_the_env_says(tmp_path):
    env = dict(
        os.environ,
        PYTHONPATH="src",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
    )
    args = "--width 64 --depth 2 --nodes 200 --edges 512 --batch 256"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", *args.split()],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "[serve]" in proc.stdout
    assert any((tmp_path / "cache").iterdir())
