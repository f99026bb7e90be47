"""Shared benchmark plumbing: result records, CSV printing, subprocess
runners for multi-device cases (the main process keeps 1 host device)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

OUT_DIR = os.environ.get("REPRO_BENCH_OUT", "experiments/bench")
SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def save(name: str, payload) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
    return path


def print_rows(name: str, rows: list[dict]):
    if not rows:
        print(f"# {name}: (no rows)")
        return
    cols = list(rows[0].keys())
    print(f"# {name}")
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r.get(c, "")) for c in cols))


def run_subprocess(code: str, devices: int = 8, timeout: float = 1200.0):
    """Run python code with N forced host devices; expects a final JSON line.

    The child is a CPU emulation by design: it is pinned to the CPU
    backend, so on a TPU host it never competes for the chip."""
    prog = textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import json
        {textwrap.indent(textwrap.dedent(code), '        ').lstrip()}
    """)
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=timeout, env=env)
    if p.returncode != 0:
        raise RuntimeError(f"subprocess failed:\n{p.stderr[-2000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.seconds = time.perf_counter() - self.t0
