"""`chip_smoke.py` on the CPU: it refuses to run without a TPU, its step
functions agree with the plain reference at a tiny size, a device kernel
that raises is never hidden under ``mode=force`` (and is counted and named
in ``auto``), and the compile cache lives where it is told to."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from delta_tpu.ops import key_cache  # noqa: E402
from delta_tpu.ops.column_cache import ColumnCache  # noqa: E402
from delta_tpu.ops.state_cache import DeviceStateCache  # noqa: E402
from delta_tpu.parallel import link  # noqa: E402
from delta_tpu.utils import jaxcache  # noqa: E402

ROWS, SOURCE_ROWS = 20_000, 2_000
AUTO = {"delta.tpu.merge.devicePath.mode": "auto"}


@pytest.fixture(autouse=True)
def _fresh_device_caches():
    for cache in (key_cache.KeyCache, ColumnCache, DeviceStateCache):
        cache.reset()
    yield
    for cache in (key_cache.KeyCache, ColumnCache, DeviceStateCache):
        cache.reset()


def test_smoke_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("fails", [False, True])
def test_last_stdout_line_is_the_verdict_and_nothing_else(
        tmp_path, monkeypatch, capsys, fails):
    """The driver reads the last stdout line: one JSON object with exactly
    ``ok`` and ``device`` (platform, kind, count). The long summary is the
    line before it."""
    import json

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def run(self):
        if fails:
            raise chip_smoke.SmokeFailure("a check failed")
        return {"seed": self.seed}

    monkeypatch.setattr(chip_smoke, "preflight", lambda: {
        "device": dict(device), "cache": {"dir": str(tmp_path)}})
    monkeypatch.setattr(chip_smoke, "link_profile", dict)
    monkeypatch.setattr(chip_smoke.Smoke, "run", run)
    assert chip_smoke.main([]) == (1 if fails else 0)
    summary, verdict = capsys.readouterr().out.splitlines()[-2:]
    assert json.loads(verdict) == {"ok": not fails, "device": device}
    assert summary.startswith("summary: ")
    assert json.loads(summary[len("summary: "):])["ok"] is (not fails)


def test_smoke_steps_match_reference_at_tiny_size(tmp_path):
    out = chip_smoke.Smoke(str(tmp_path), 0, ROWS, SOURCE_ROWS).run()
    decisions = [m["decision"] for m in out["merges"]]
    # conftest's virtual 8-device mesh adds the shard_map MERGE leg
    assert decisions[:2] == ["device-cold", "resident"]
    assert decisions[3:] == ["device-upload"]
    assert all((m["updated"], m["inserted"]) == (1000, 1000)
               for m in out["merges"])
    assert out["sizes"]["final_rows"] == ROWS + len(decisions) * 1000
    assert out["counters"]["merge.device.engaged"] == 2
    assert out["counters"]["scan.device.engaged"] == 14 == len(out["scans"])
    assert [s["plan"] for s in out["scans"]] == (
        ["resident"] * 6 + ["device-prune", "resident"]  # the 10M table
        + ["device-prune"] * 2 + ["resident"] + ["device-prune"] * 2  # edges
        + ["resident"])
    assert out["counters"]["scan.device.fallback"] == 0
    assert out["counters"]["scan.prune.deviceFallback"] == 0
    assert out["counters"]["dist.degraded.plan"] == 0


def test_smoke_float_scans_fail_on_a_float32_pair_device(tmp_path, monkeypatch):
    """What the float scans are for: were float lanes and literals held as
    a TPU holds float64 — a float32 pair — every one of them would lose or
    gain a row against the reference."""
    import numpy as np

    from delta_tpu.expr import jaxeval

    exact_key = jaxeval.f64_order_key

    def f32_pair_key(values):
        with np.errstate(over="ignore", invalid="ignore"):
            x = np.asarray(values, np.float64)
            hi = x.astype(np.float32).astype(np.float64)
            lo = np.where(np.isfinite(hi), x - hi, 0).astype(np.float32)
            return exact_key(hi + lo)

    monkeypatch.setattr(jaxeval, "f64_order_key", f32_pair_key)
    smoke = chip_smoke.Smoke(str(tmp_path), 0, ROWS, SOURCE_ROWS)
    smoke.load()
    failed = []
    scan = smoke.scan

    def scan_recording_failures(label, terms, **kw):
        try:
            return scan(label, terms, **kw)
        except chip_smoke.SmokeFailure as e:
            failed.append(str(e))
            return {"rows": 1}

    monkeypatch.setattr(smoke, "scan", scan_recording_failures)
    smoke.float_scans()
    assert len(failed) == 2 + len(chip_smoke.EDGE_FILTERS), failed
    assert all("rows, reference has" in f for f in failed)


def _refusing_kernel():
    def kernel(*_args):
        raise RuntimeError("refused by the compiler")

    return kernel


def test_forced_merge_raises_when_the_probe_kernel_raises(tmp_path, monkeypatch):
    smoke = chip_smoke.Smoke(str(tmp_path), 0, ROWS, SOURCE_ROWS)
    smoke.load()
    version = smoke.table.version
    monkeypatch.setattr(key_cache, "_probe_sorted_kernel", _refusing_kernel)
    with pytest.raises(RuntimeError, match="refused by the compiler"):
        smoke.merge("forced", chip_smoke.FORCE_MERGE)
    assert smoke.table.version == version  # nothing committed
    assert smoke.counters("merge.device.engaged",
                          "merge.device.fallback") == {
        "merge.device.engaged": 0, "merge.device.fallback": 0}


def test_auto_merge_falls_back_counted_with_the_exception_on_the_router(
        tmp_path, monkeypatch):
    smoke = chip_smoke.Smoke(str(tmp_path), 0, ROWS, SOURCE_ROWS)
    smoke.load()
    monkeypatch.setattr(key_cache, "_probe_sorted_kernel", _refusing_kernel)
    # price the host join out so the router picks the device at this size
    link.set_calibrated("HOST_JOIN_S_PER_ROW", 1.0)
    try:
        rec = smoke.merge("auto", AUTO, expect="host")
    finally:
        link.clear_calibrated()
    assert rec["router"]["reason"] == "device-finalize-fallback"
    assert rec["router"]["error"] == "RuntimeError: refused by the compiler"
    assert smoke.counters("merge.device.fallback",
                          "merge.device.engaged") == {
        "merge.device.fallback": 1, "merge.device.engaged": 0}
    smoke.read_back()  # the host join's MERGE is the reference's


# -- where the compile cache lives -------------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    """`jaxcache` re-armed, with its ``jax.config.update`` calls recorded
    instead of applied."""
    import jax

    calls = []
    monkeypatch.setattr(jaxcache, "_done", False)
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_jaxcache_honours_env_without_touching_jax_config(
        config_updates, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    jaxcache.ensure_compilation_cache()
    assert config_updates == []
    assert jaxcache.cache_dir() == str(tmp_path / "xla")
    assert not (tmp_path / "xla").exists()  # JAX's to create, not ours


def test_jaxcache_defaults_to_the_checkout(config_updates, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jaxcache.ensure_compilation_cache()
    jaxcache.ensure_compilation_cache()  # once
    assert config_updates == [
        ("jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))]
    assert os.path.isdir(os.path.join(REPO, ".jax_cache"))


def test_jaxcache_uncreatable_directory_is_an_error_naming_the_path(
        config_updates, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def denied(path, exist_ok=False):
        raise PermissionError(13, "Permission denied", path)

    monkeypatch.setattr(jaxcache.os, "makedirs", denied)
    with pytest.raises(RuntimeError, match=r"\.jax_cache"):
        jaxcache.ensure_compilation_cache()
    assert config_updates == []
