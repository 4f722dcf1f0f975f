"""chip_smoke.py rehearsed on the CPU, and the compile-cache helper.

The chip run itself needs a chip (``chiprun -- python chip_smoke.py``);
what can be guarded here is that the script's whole control flow — load,
HTTP serve, reference checks, the storm through the wave kernel
(interpret mode), the stats it asserts on — still runs end to end, and
that it refuses to call a CPU run ok.
"""

import json
import os
import sys

import jax
import pytest

from spark_druid_olap_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rehearsal_runs_every_phase(monkeypatch, capsys):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke
    monkeypatch.setenv("SDOT_PALLAS", "interpret")
    monkeypatch.setattr(sys, "argv",
                        ["chip_smoke.py", "--rehearse", "--sf", "0.01"])
    chip_smoke.main()
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert set(last) == {"ok", "device"}, last
    assert last["ok"] is False, "a CPU rehearsal must never report ok"
    assert last["device"]["platform"] == "cpu"

    by_phase = {}
    for ln in lines[:-1]:
        by_phase.setdefault(ln["phase"], []).append(ln)
    assert {"device", "cache", "load", "serve", "storm_lane",
            "storm"} <= set(by_phase), sorted(by_phase)
    served = {ln["statement"]: ln for ln in by_phase["serve"]}
    assert set(served) == set(chip_smoke.SERVED)
    for name, ln in served.items():
        assert ln["correct"] and ln["cold"]["mode"] == "engine", (name, ln)
        assert ln["cold"]["n_dispatch"] >= 1, (name, ln)
    assert len(by_phase["storm_lane"]) == len(chip_smoke.STORM) == 8
    storm = by_phase["storm"][0]
    assert storm["correct"] and storm["interpret"]
    assert storm["queries_coalesced"] >= 8 and storm["fallbacks"] == 0
    assert storm["pallas"]["launches"] >= 1
    assert storm["pallas"]["fallbacks"] == 0


def test_no_tpu_exits_before_loading(monkeypatch, capsys):
    """Without --rehearse a CPU backend is a non-zero exit and no result
    line — before any data is generated."""
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    monkeypatch.setattr(chip_smoke, "phase_load",
                        lambda *a, **k: pytest.fail("loaded data"))
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)
        # the variable is JAX's to read: nothing was set in code
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV_VAR)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.configure() == want == compile_cache.CHECKOUT_DIR
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.entries(str(tmp_path / "absent")) == 0
        (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
        (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
        assert compile_cache.entries(str(tmp_path)) == 1
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
