"""The shipped tutorial notebook must actually run.

The reference ships ``scripts/tutorial.ipynb`` as living documentation; ours
is TPU-native (`scripts/tutorial.ipynb`). The notebook is executed in a
*fresh subprocess with a clean environment* — 32-bit JAX defaults, no
conftest x64 flag, device count coming from the notebook's own first cell —
so it is validated in the environment users actually run it in, and
documentation rot shows up as a test failure, not a user bug report.
"""

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

_RUNNER = """
import json, sys
cells = [
    "".join(c["source"])
    for c in json.load(open(sys.argv[1]))["cells"]
    if c["cell_type"] == "code"
]
ns = {}
for i, src in enumerate(cells):
    try:
        exec(compile(src, f"<tutorial cell {i}>", "exec"), ns)
    except Exception:
        import traceback

        traceback.print_exc()
        print(f"FAILED at cell {i}:", src[:120])
        sys.exit(1)
print(f"OK {len(cells)} cells")
"""


def test_tutorial_notebook_cells_execute():
    nb_path = REPO / "scripts" / "tutorial.ipynb"
    nb = json.loads(nb_path.read_text())
    n_code = sum(1 for c in nb["cells"] if c["cell_type"] == "code")
    assert n_code >= 20, "tutorial shrank suspiciously"

    env = {
        k: v
        for k, v in os.environ.items()
        # scrub everything the test harness injects: the notebook's first
        # cell must be the thing that configures the mesh
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_ENABLE_X64", "HEAT_TPU_TEST_DEVICES")
    }
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _RUNNER, str(nb_path)],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}"
    assert f"OK {n_code} cells" in proc.stdout


def test_interactive_script_importable():
    # the REPL script must at least parse and expose main()
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "heat_interactive", REPO / "scripts" / "interactive.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.main)
