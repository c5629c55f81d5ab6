"""The trace reduction on traces recorded on a v5e chip (fixtures made
with ``devtrace.to_json(devtrace.from_xplane(...))`` from 8 runs of each
one-chip cell's program) and checked here by plain sums."""

import json
import os

import pytest

import devtrace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def load(name):
    with open(os.path.join(FIXTURES, f"trace_{name}.json")) as fd:
        return devtrace.from_json(json.load(fd))


def plain_busy_ns(ops):
    """Union length by walking the ops in start order."""
    total, reach = 0, None
    for _, s, d in sorted(ops, key=lambda o: o[1]):
        e = s + d
        if reach is None or s > reach:
            total += d
            reach = e
        elif e > reach:
            total += e - reach
            reach = e
    return total


@pytest.mark.parametrize("name,module,kernel", [
    ("p46gun_big", r"^jit_advance\(", "custom-call:_run_vmem_bits_jit.1"),
    ("pod8192", r"^jit__run_fused_bits_jit\(", "custom-call:body.3"),
])
def test_recorded_trace(name, module, kernel):
    t = load(name)
    (dev,) = t.devices.values()
    assert t.busy_s() == pytest.approx(plain_busy_ns(dev["ops"]) / 1e9)
    runs = [m for m in dev["modules"] if m[0].startswith(module[1:-2])]
    assert len(runs) == 8
    assert t.module_s(module) == pytest.approx(sum(m[2] for m in runs) / 1e9)
    assert 0 < t.module_s(module) <= 1.01 * t.busy_s()
    b = t.breakdown()
    assert b["device_ops"][0][0] == kernel
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert t.op_share("^collective-permute") is None


def test_recorded_2x2_trace():
    """Three runs of the 2x2 cell's program on four chips."""
    t = load("pod8192_cart2x2")
    assert sorted(t.devices) == [f"/device:TPU:{i}" for i in range(4)]
    want = []
    for dev in t.devices.values():
        busy = plain_busy_ns(dev["ops"])
        coll = sum(d for name, _, d in dev["ops"]
                   if name.startswith("collective-permute"))
        want.append(coll / busy)
    # collective-permute ops hold no other op, so self time is duration
    assert t.op_share("^collective-permute") == pytest.approx(
        sum(want) / 4)
    assert 0 < t.op_share("^collective-permute") < 0.1
    b = t.breakdown()
    assert b["device_ops"][0][0].startswith("custom-call:")
    assert b["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert t.module_s(r"^jit_advance\(") > 0
