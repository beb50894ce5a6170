"""``chip_smoke.py``'s check functions at tiny sizes on the CPU mesh (pallas
kernels interpreted), and the no-chip-no-run contract of the two entry
points that demand the chip."""

import os
import subprocess
import sys

import pytest

import heat_tpu as ht

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402

sys.path.pop(0)


@pytest.fixture(autouse=True)
def _smoke_warnings():
    """The same warnings-are-errors regime ``chip_smoke.main`` runs under."""
    import warnings

    with warnings.catch_warnings():
        chip_smoke.arm_warnings()
        yield


def test_kmeans_fused_interpret_matches_oracle():
    out = chip_smoke.check_kmeans(n=4099, f=16, k=8, iters=10, interpret=True)
    assert out["interpret"] is True
    if ht.get_comm().size > 1:
        assert out["mode"] == "sharded"
        assert out["lloyd_collectives"]["all-reduce"] >= 1
        assert len(out["shard_devices"]) == ht.get_comm().size


def test_analytics_checks():
    chip_smoke.check_cdist(n=512, f=8, block=32)
    chip_smoke.check_moments(n=10_000)
    chip_smoke.check_qr(m=2048, n=16, rows=64)
    chip_smoke.check_eager(rows=1003, cols=7)


def test_server_check():
    out = chip_smoke.check_server(clients=4, requests=5, n=256)
    assert out == {"retraces": 0, "requests": 20}


def test_trainer_checks():
    chip_smoke.check_trainer(batch=16, steps=4, model=ht.nn.SimpleCNN(num_classes=10))
    if ht.get_comm().size > 1 and ht.get_comm().size % 2 == 0:
        out = chip_smoke.check_daso(batch=16, model=ht.nn.SimpleCNN(num_classes=10))
        assert out["mesh"]["dcn"] == 2


def test_kernel_checks_interpreted():
    # chip_smoke.main audits a fresh process; an xdist worker has run other
    # files first, and test_resilience.py's last degraded force leaves its
    # quarantined program behind (degraded 1, quarantined 1: it turned this
    # test red once, PR 32, when both files fell to one worker)
    from heat_tpu.core import fusion

    fusion.clear_cache()
    chip_smoke.check_kernels(n=256, f=8, seq=256, heads=2, dim=16, interpret=True)
    chip_smoke.check_nothing_swallowed()


def test_verdict_line_has_exactly_the_contract_keys():
    facts = chip_smoke.device_facts()
    for failed in ([], ["qr"]):
        line = chip_smoke.verdict(facts, failed)
        assert set(line) == {"ok", "device"} and line["ok"] is (not failed)
        assert set(line["device"]) == {"platform", "kind", "count"}
        assert isinstance(line["device"]["platform"], str)
        assert isinstance(line["device"]["kind"], str)
        assert type(line["device"]["count"]) is int


def test_entry_point_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout
    assert "no TPU found" in proc.stderr, proc.stderr[-2000:]
