"""API parity check: every public name exported by the reference's __all__
lists must be reachable in heat_tpu (same top-level or submodule location).

Run:  python scripts/api_parity_check.py [/path/to/reference/heat]
Exit code 1 and a listing if anything is missing. Used by
tests/test_api_aliases.py when the reference checkout is present.
"""

import ast
import os
import sys

# Running as ``python scripts/api_parity_check.py`` puts scripts/ (not the
# repo root) on sys.path.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

SUBMODULES = (
    "nn", "optim", "cluster", "spatial", "utils", "linalg", "random",
    "datasets", "classification", "naive_bayes", "regression", "graph",
)


def reference_names(ref_root):
    names = {}
    for dirpath, _, files in os.walk(ref_root):
        if "tests" in dirpath:
            continue
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                tree = ast.parse(open(path).read())
            except SyntaxError:  # pragma: no cover
                continue
            for node in ast.walk(tree):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Name) and t.id == "__all__":
                            try:
                                vals = ast.literal_eval(node.value)
                            except Exception:  # pragma: no cover
                                continue
                            rel = os.path.relpath(path, ref_root)
                            for v in vals:
                                names.setdefault(v, []).append(rel)
    return names


def missing_names(ref_root):
    import heat_tpu as ht
    import heat_tpu.utils.data  # noqa: F401 - reachable data namespace

    out = []
    for name, sources in sorted(reference_names(ref_root).items()):
        if name.startswith("_"):
            continue
        found = hasattr(ht, name)
        if not found:
            for sub in SUBMODULES:
                mod = getattr(ht, sub, None)
                if mod is not None and hasattr(mod, name):
                    found = True
                    break
                if sub == "utils" and hasattr(ht.utils.data, name):
                    found = True
                    break
        if not found:
            out.append((name, sources[0]))
    return out


def main():
    ref_root = sys.argv[1] if len(sys.argv) > 1 else "/root/reference/heat"
    miss = missing_names(ref_root)
    total = len([n for n in reference_names(ref_root) if not n.startswith("_")])
    print(f"reference public names: {total}; missing in heat_tpu: {len(miss)}")
    for n, src in miss:
        print(f"  {n}  ({src})")
    sys.exit(1 if miss else 0)


if __name__ == "__main__":
    main()
