"""What ``chipbench/tests`` need of a configuration added after they were
written, without an edit to their files.

``tests/tiny.py`` holds the CPU sizes of each configuration in a dictionary
and ``tests/test_rehearsal.py`` runs every cell of ``BENCHMARK.json`` through
it, so a new configuration brings its tiny sizes here. One case of that file
plants a fault in ``ht.mean`` / ``ht.std`` and expects ``mean_gap`` and
``std_gap`` of every cell to catch it; a k-means fit calls neither, so that
case is expected to fail for ``kmeans_fit_1c`` (strictly: if it ever passes,
this note is stale). The fault that a fit has to catch, rows left out, is in
``tests/test_kmeans_cell.py``.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# On the tests' four virtual CPU devices KMeans takes the jnp path by itself.
# Few rows: the program scores by the quadratic expansion and the reference by
# direct differences, so about one row in 10^6 per iteration is a tie within
# rounding, and at a CPU size ONE row assigned otherwise moves a centre by more
# than the limits allow (at 2^26 rows it moves it by 1e-7). 512 rows over 30
# iterations meet such a tie in one fit of a hundred.
TINY_KMEANS = {"rows_per_chip": 128, "lloyd_mode": {"4": "jnp"}}
NOT_A_KMEANS_FAULT = "test_fault_half_of_the_rows_left_out[kmeans_fit_1c]"


def pytest_configure(config):
    from chipbench.tests import tiny

    tiny.TINY.setdefault("kmeans_f32_k8", TINY_KMEANS)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == NOT_A_KMEANS_FAULT:
            item.add_marker(pytest.mark.xfail(strict=True, reason="the planted fault is in ht.mean/ht.std, which a fit does not call"))
