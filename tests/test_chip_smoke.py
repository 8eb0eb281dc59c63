"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide, section 2):
the script end to end at `tpch.tiny` behind the test-only platform argument
of `chip_smoke.run` — one-chip path, and the `--chips 4` path on four of
the eight virtual devices — plus the contract's refusals (no accelerator ->
non-zero exit and no `ok` line) and the one compile-cache placement rule."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402
from trino_tpu.parallel import spmd  # noqa: E402


def _facts(out: str) -> list:
    return [json.loads(l) for l in out.splitlines() if l.startswith("{")]


def test_one_chip_path_rehearsal(capsys):
    rc = chip_smoke.run(
        chips=1, platform="cpu", schema="tiny", big_schema=None
    )
    out = capsys.readouterr().out
    facts = _facts(out)
    assert rc == 0, out[-3000:]
    # the last line is the contract's object and nothing else
    assert json.loads(out.strip().splitlines()[-1]) == {
        "ok": True,
        "device": {
            "platform": "cpu", "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
    }
    ran = {
        (f["runner"], f["query"]) for f in facts
        if "runner" in f and "rows" in f
    }
    assert {("local", f"q{q}") for q in (1, 6, 3, 18)} <= ran
    assert {("server+client", f"q{q}") for q in (1, 6, 3, 18)} <= ran
    assert {("distributed", "q1"), ("distributed", "broadcast_join")} <= ran
    matched = {
        f["query"]: f["matches"] for f in facts
        if f.get("runner") == "local" and "matches" in f
    }
    assert matched["q1"] == ["exact_int64", "pandas_oracle"]
    assert matched["q18"] == ["pandas_oracle"]
    assert any(f.get("query") == "pallas_agg" for f in facts)
    summary = next(f for f in facts if f.get("summary"))
    assert summary["failed"] == []


def test_four_chip_path_rehearsal(capsys):
    """`--chips 4` on four of the eight virtual devices; tiny tables would
    all broadcast, so the test lowers the threshold to plan Q3's joins
    partitioned as SF1 does."""
    rc = chip_smoke.run(
        chips=4, platform="cpu", schema="tiny",
        session={"broadcast_join_rows": 100},
    )
    out = capsys.readouterr().out
    facts = _facts(out)
    assert rc == 0, out[-3000:]
    assert json.loads(out.strip().splitlines()[-1])["ok"] is True
    phases = {f["phase"]: f["passed"] for f in facts if "phase" in f}
    assert phases == {
        "mesh:repartitioned_agg": True,
        "mesh:broadcast_join": True,
        "mesh:partitioned_join_q3": True,
        "mesh:scan_placement": True,
    }  # and no other phase
    placement = next(f for f in facts if f.get("check") == "scan_placement")
    assert placement["devices"] == [d.id for d in jax.devices()[:4]]


def test_a_failed_phase_fails_the_run(capsys, monkeypatch):
    def boom(schema):
        raise AssertionError("kernel answered wrong")

    monkeypatch.setattr(chip_smoke, "pallas_agg_query", boom)
    monkeypatch.setattr(chip_smoke, "local_query", lambda *a, **k: [])
    monkeypatch.setattr(chip_smoke, "server_path", lambda *a, **k: None)
    monkeypatch.setattr(chip_smoke, "mesh_of_one", lambda *a, **k: None)
    rc = chip_smoke.run(
        chips=1, platform="cpu", schema="tiny", big_schema=None
    )
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out
    assert "kernel answered wrong" in out


def test_script_refuses_to_run_without_a_tpu():
    """As the driver runs it, in a sandbox with no accelerator: non-zero
    exit, no result line."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300, cwd=REPO_ROOT,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no accelerator" in r.stderr


# -- the one compile-cache placement rule (spmd.configure_persistent_cache) ----


@pytest.fixture
def cache_rule(monkeypatch):
    """Spy on jax.config.update; put the suite's placement back after."""
    updates = []
    real = jax.config.update

    def spy(name, value):
        updates.append(name)
        real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    yield updates
    monkeypatch.undo()
    spmd.configure_persistent_cache()


@pytest.mark.parametrize("configured", ["", "/some/deployment/dir"])
def test_env_var_set_means_no_dir_is_set_in_code(
    cache_rule, monkeypatch, tmp_path, configured
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert spmd.configure_persistent_cache(configured) == str(tmp_path)
    assert "jax_compilation_cache_dir" not in cache_rule


def test_env_var_unset_means_the_fixed_in_checkout_dir(
    cache_rule, monkeypatch
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert spmd.DEFAULT_CACHE_DIR == want
    assert spmd.configure_persistent_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # idempotent: the same answer, not a new (temp, pid, time) name
    assert spmd.configure_persistent_cache() == want


def test_explicit_dir_wins_over_the_default_only(
    cache_rule, monkeypatch, tmp_path
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert spmd.configure_persistent_cache(str(tmp_path)) == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert spmd.configure_persistent_cache(enabled=False) is None
