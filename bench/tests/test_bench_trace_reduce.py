"""The trace reduction on a small synthetic event list."""
import pytest

import tinyroot  # noqa: F401
import trace_reduce as tr

MS = 1e6        # ns


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert tr.union_length(iv) == 15 + 11
    assert tr.gaps(iv, -5, 40) == [(-5, 0), (15, 20), (31, 40)]
    assert tr.union_length([]) == 0


def test_reduce_events_splits_kernels_and_labels_gaps():
    host = [("bench.traced", 0, 100 * MS),
            ("bench.serve", 0, 60 * MS),
            ("PjitFunction(edge_score)", 40 * MS, 10 * MS),
            ("bench.wait", 60 * MS, 40 * MS)]
    ops = [(0, "custom-call.1", 10 * MS, 30 * MS, True),   # kernel 10-40
           (0, "fusion.2", 50 * MS, 10 * MS, False),       # XLA 50-60
           (0, "copy.3", 90 * MS, 20 * MS, False),         # 90-110, clipped
           (1, "custom-call.1", 10 * MS, 10 * MS, True),
           (0, "fusion.9", -20 * MS, 10 * MS, False)]      # before the window
    red = tr.reduce_events(ops, host)
    assert red["window_s"] == pytest.approx(0.1)
    c0, c1 = red["chips"]
    assert c0["busy_s"] == pytest.approx(0.050)     # 10-40, 50-60, 90-100
    assert c0["pallas_s"] == pytest.approx(0.030)
    assert c0["xla_s"] == pytest.approx(0.020)
    assert c1["busy_s"] == pytest.approx(0.010)
    gaps = red["idle_gaps"]
    assert gaps[0] == ["TPU:1 bench.wait", pytest.approx(0.080)]      # 20-100
    assert ["TPU:0 bench.wait", pytest.approx(0.030)] in gaps          # 60-90
    assert ["TPU:0 bench.serve / PjitFunction(edge_score)",
            pytest.approx(0.010)] in gaps                             # 40-50
    assert ["TPU:0 bench.serve", pytest.approx(0.010)] in gaps         # 0-10
    names = [n for n, _ in red["device_ops"]]
    assert names[0] == "custom-call.1"


def test_window_span_is_required():
    with pytest.raises(ValueError):
        tr.reduce_events([], [("bench.serve", 0, 1)])


@pytest.mark.parametrize("name,stats,want", [
    ("custom-call.4", {"long_name": "custom-call(...), custom_call_target=\"tpu_custom_call\""}, True),
    ("tpu_custom_call.1", {}, True),
    ("fusion.3", {"long_name": "fusion(...)"}, False)])
def test_is_pallas(name, stats, want):
    assert tr.is_pallas(name, stats) is want


def test_op_names_are_short_and_carry_their_module():
    hlo = "%fusion.1 = f32[9437184,3]{0,1:T(4,128)} fusion(f32[8294400,3] %a.1)"
    assert tr.op_name(hlo, "jit__take(4635585172558814308)") == "jit__take/fusion.1"
    assert tr.op_name(hlo) == "fusion.1"
    modules = [(0, 10, "jit_a(1)"), (20, 30, "jit_b(2)")]
    assert tr._module_at(modules, 5) == "jit_a(1)"
    assert tr._module_at(modules, 25) == "jit_b(2)"
    assert tr._module_at(modules, 15) == ""
