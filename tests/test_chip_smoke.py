"""``chip_smoke.py``'s phases on the CPU at ``small-12-8``.

The script itself refuses any backend but a TPU; these tests drive its
phase functions directly: the served waves, the oracle check, the Pallas
wave against the jnp wave (interpret mode here), and the 4-device sharded
phase on forced host devices in a subprocess.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
SMALL = "small-12-8"


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lines(text: str) -> dict:
    return dict(l.split(": ", 1) for l in text.splitlines() if ": " in l)


def test_one_chip_phases_on_small_graph(cs, capsys):
    cs.one_chip(graph=SMALL, pallas_graph=SMALL)
    out = _lines(capsys.readouterr().out)
    assert out["graph"] == SMALL
    assert out["requests_served"] == f"{cs.REQUESTS} of {cs.REQUESTS}"
    assert int(out["waves"]) >= 2
    assert out["oracle_roots_matched"] == f"{cs.CHECK_ROOTS} of " \
                                          f"{cs.CHECK_ROOTS}"
    assert out["pallas_wave_bit_exact"] == "True"
    assert len(json.loads(out["wave_seconds"])) == int(out["waves"])


class _FailingEngine:
    num_vertices = 16
    out_deg = np.ones(16, np.int64)

    def run_batch(self, roots):
        raise RuntimeError("injected engine fault")


def test_a_failed_request_fails_the_run(cs):
    with pytest.raises(cs.SmokeFailure, match="failed"):
        cs.serve(_FailingEngine(), _FailingEngine.out_deg, np.arange(4),
                 max_batch=32, window=0.0)


def test_oracle_mismatch_fails_the_run(cs):
    from repro.graph import get_dataset
    csr = get_dataset(SMALL).csr
    root = int(np.flatnonzero(np.diff(csr.indptr) > 0)[0])
    from repro.core import bfs_oracle
    row = bfs_oracle(csr, root)
    assert cs.check_rows(csr, [root], [row]) == 1
    row = row.copy()
    row[row == 1] = 2
    with pytest.raises(cs.SmokeFailure, match="differ"):
        cs.check_rows(csr, [root], [row])


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_cpu_backend(cs, capsys, argv):
    assert cs.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_sharded_phase_on_four_host_devices():
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r}); "
            f"import chip_smoke; chip_smoke.sharded(4, graph={SMALL!r})")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    out = _lines(p.stdout)
    assert out["graph_arrays_span_devices"] == "4"
    assert out["oracle_roots_matched"] == f"4 of 4"


def test_script_alone_fails_without_a_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
