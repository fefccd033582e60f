"""Where the trainers run: the device mapping of a host with accelerators
(stand-in device lists), the peaks lookup by device kind, and gradient
sync between trainers on two different devices (forced host devices in a
subprocess, against the same step on one device)."""
import collections
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Synchronizer
from repro.core.hybrid import resolve_trainer_devices
from repro.core.perfmodel import PLATFORMS, platform_for_device_kind

Dev = collections.namedtuple("Dev", "platform id")
CPUS = [Dev("cpu", 0)]
TPUS = [Dev("tpu", i) for i in range(4)]


@pytest.mark.parametrize("n_accel", [0, 1, 4])
def test_accelerators_map_to_their_own_tpu(n_accel):
    cpu, accel = resolve_trainer_devices(n_accel, CPUS, TPUS)
    assert cpu == Dev("cpu", 0)
    assert accel == [Dev("tpu", i) for i in range(n_accel)]


def test_more_accelerators_than_tpus_is_an_error():
    with pytest.raises(ValueError, match="n_accel=2"):
        resolve_trainer_devices(2, CPUS, TPUS[:1])


def test_host_devices_stand_in_without_accelerators():
    hosts = [Dev("cpu", i) for i in range(2)]
    cpu, accel = resolve_trainer_devices(3, hosts, [])
    assert cpu == hosts[0]
    assert accel == [hosts[1], hosts[0], hosts[1]]
    cpu, accel = resolve_trainer_devices(2, CPUS, [])
    assert accel == [CPUS[0], CPUS[0]]


def test_peaks_follow_the_device_kind():
    assert platform_for_device_kind("TPU v5 lite") == "tpu-v5e"
    assert PLATFORMS[platform_for_device_kind("TPU v5 lite")].peak_tflops \
        == 197.0
    with pytest.raises(ValueError, match="no peaks for device kind"):
        platform_for_device_kind("TPU v99")


_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + sys.argv[1])
import jax, jax.numpy as jnp
from repro.core import HybridConfig, HybridGNNTrainer, Synchronizer
from repro.graph import GNNConfig, make_dataset

devs = jax.devices()
sync = Synchronizer(2, device=devs[0])
sync.submit(0, {"w": jax.device_put(jnp.ones(4), devs[0])}, 3.0)
sync.submit(1, {"w": jax.device_put(jnp.full(4, 5.0), devs[-1])}, 1.0)
avg = sync.all_reduce()

ds = make_dataset("ogbn-products", scale=0.003, seed=0)
g = GNNConfig(model="sage", layer_dims=(100, 64, 47), fanouts=(4, 3),
              num_classes=47)
tr = HybridGNNTrainer(ds, g, HybridConfig(
    total_batch=256, n_accel=1, hybrid=True, use_drm=False, tfp_depth=0,
    cache_fraction=0.2, share_quantum=32, seed=0))
hist = tr.train(2)
tr.close()
print("RESULT:" + json.dumps({
    "avg": [float(x) for x in avg["w"]],
    "avg_device": str(next(iter(avg["w"].devices()))),
    "losses": [m.loss.hex() for m in hist],
    "shares": [list(m.assignment) for m in hist],
    "grad_devices": hist[-1].grad_devices,
}))
"""


def _run(n_devices: int) -> dict:
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, str(n_devices)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    return json.loads(line[0][len("RESULT:"):])


def test_sync_and_step_across_two_devices_match_one_device():
    """CPU trainer and accel0 on different devices: the synchronizer sums
    on the parameters' device, and the losses are bit-identical to the
    run with both trainers on one device."""
    two, one = _run(2), _run(1)
    np.testing.assert_array_equal(two["avg"], np.full(4, 2.0))
    assert two["avg_device"] == "TFRT_CPU_0"
    assert all(cpu > 0 and acc > 0 for cpu, acc in two["shares"])
    assert two["grad_devices"] == {"cpu": "cpu:0", "accel0": "cpu:1"}
    assert one["grad_devices"] == {"cpu": "cpu:0", "accel0": "cpu:0"}
    assert two["losses"] == one["losses"]


def test_synchronizer_without_device_sums_in_place():
    """Gradients already on the parameters' device are summed there."""
    dev = jax.devices()[0]
    sync = Synchronizer(2, device=dev)
    sync.submit(0, {"w": jax.device_put(jnp.ones(2), dev)}, 1.0)
    sync.submit(1, {"w": jax.device_put(jnp.full((2,), 3.0), dev)}, 1.0)
    avg = sync.all_reduce()["w"]
    assert avg.devices() == {dev}
    np.testing.assert_array_equal(np.asarray(avg), np.full(2, 2.0))


class _ReadsTpu:
    """A host device whose platform reads as a v5e's: the trainer's
    routing sees a TPU, and whatever it places still lands on the host."""
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def __init__(self, dev):
        self._dev = dev

    def __getattr__(self, name):
        return getattr(self._dev, name)


@pytest.mark.parametrize("assemble,pallas_combine", [("auto", False),
                                                     ("pallas", True)])
def test_combine_routing_on_a_tpu(monkeypatch, assemble, pallas_combine):
    """On a TPU, "auto" takes XLA's gather for the combine and keeps the
    Pallas cache-update kernel; "pallas" forces both kernels."""
    from repro.core import HybridConfig, HybridGNNTrainer, hybrid
    from repro.graph import GNNConfig, make_dataset
    monkeypatch.setattr(
        hybrid, "resolve_trainer_devices",
        lambda n, cpus, accels: (cpus[0], [_ReadsTpu(cpus[0])] * n))
    ds = make_dataset("ogbn-products", scale=0.002, seed=0)
    g = GNNConfig(model="sage", layer_dims=(100, 32, 47), fanouts=(4, 3),
                  num_classes=47)
    tr = HybridGNNTrainer(ds, g, HybridConfig(
        total_batch=128, n_accel=1, hybrid=True, use_drm=False,
        cache_fraction=0.2, cache_assemble=assemble, seed=0))
    try:
        assert tr.accel_devices[0].platform == "tpu"
        assert tr._assemble_pallas is pallas_combine
        assert tr.cache.use_pallas_update is True
    finally:
        tr.close()
