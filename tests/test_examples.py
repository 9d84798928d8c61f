"""Every example under ``examples/`` runs to exit 0 on the CPU at its own
small defaults (each in a fresh subprocess, so the examples' JAX state stays
out of the test process)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (script, extra arguments beyond its defaults, seconds allowed)
EXAMPLES = [
    ("quickstart.py", [], 180),
    ("genomics_phewas.py", ["--out", "{tmp}"], 180),
    ("dataset_dedup.py", [], 180),
]


def test_every_example_is_listed():
    shipped = {f for f in os.listdir(os.path.join(ROOT, "examples"))
               if f.endswith(".py")}
    assert shipped == {script for script, _, _ in EXAMPLES}


@pytest.mark.parametrize("script,args,timeout", EXAMPLES,
                         ids=[script for script, _, _ in EXAMPLES])
def test_example_runs(tmp_path, script, args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    argv = [a.format(tmp=tmp_path) for a in args]
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", script), *argv],
        capture_output=True, text=True, env=env, timeout=timeout,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
