"""chip_smoke.py rehearsed on the CPU: every phase and check at a tiny
size, with the leaf-search kernel in interpret mode; and the script itself
refuses to run without a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.core import TreeConfig
from repro.core import cache as cache_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_at_tiny_size(monkeypatch):
    smoke = _load()
    # steer the caches onto the interpreted kernel; the script itself
    # accepts nothing but the compiled one
    monkeypatch.setattr(cache_mod, "default_kernel_mode",
                        lambda: "interpret")
    monkeypatch.setattr(smoke, "KERNEL_MODE", "interpret")
    cfg = TreeConfig(n_ms=4, nodes_per_ms=1 << 12, fanout=16,
                     n_locks_per_ms=4096, max_height=8, n_cs=8)
    lines = []
    rep = smoke.run(cfg, records=20_000, keyspace=1 << 16, seed=0,
                    n_clients=128, warm_rounds=4, served_rounds=2,
                    lanes=512, log=lines.append)
    assert rep["served_ops"] == 256
    assert rep["served_cache_hits"] > 0 and rep["exact_cache_hits"] > 0
    assert rep["exact_answers"] >= 5 * 512
    assert set(rep["wall_s"]) == {"bulk_load", "first_compile", "served",
                                  "exact"}
    assert any("matched" in ln for ln in lines)


def test_reference_rejects_wrong_answers():
    smoke = _load()
    ref = smoke.Reference(np.arange(4), np.arange(4))
    ref.write(np.asarray([9]), np.asarray([5]))
    q = np.asarray([1, 9, 7])            # loaded, own write, absent
    assert ref.check_lookup(q, np.asarray([1, 5, 0]),
                            np.asarray([True, True, False])) == 3
    for got, found in (([1, 6, 0], [True, True, False]),
                       ([1, 5, 0], [True, True, True]),
                       ([1, 5, 0], [False, True, False])):
        with pytest.raises(smoke.SmokeFailure):
            ref.check_lookup(q, np.asarray(got), np.asarray(found))


def test_script_refuses_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cwd in (ROOT, tmp_path):
        script = SCRIPT
        if cwd == tmp_path:         # the script alone, without the repo
            script = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
        out = subprocess.run([sys.executable, str(script)], cwd=cwd,
                             env=env, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode != 0
        assert '"ok": true' not in out.stdout
