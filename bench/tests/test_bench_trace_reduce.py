"""The trace reduction: busy and idle time, per-op time and idle gaps given
to the host span that covers them, on a small hand-made trace."""
import pytest

import trace_reduce as tr

DEV = "/device:TPU:0"


def _ev(line, name, start, dur, plane=DEV, **stats):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur, "stats": stats}


def _trace():
    return [
        _ev("", "bench.window", 0, 1000, plane="/host:CPU"),
        _ev("", "bench.tick", 0, 500, plane="/host:CPU"),
        _ev("", "bench.step_batch", 50, 100, plane="/host:CPU"),
        _ev("", "bench.tick", 600, 400, plane="/host:CPU"),
        _ev(tr.MODULES_LINE, "jit_step", 100, 300),
        _ev(tr.OPS_LINE, "fusion.1", 100, 100),
        _ev(tr.OPS_LINE, "custom-call.7", 150, 150),   # overlaps fusion.1
        _ev(tr.OPS_LINE, "fusion.1", 350, 50),
        _ev(tr.OPS_LINE, "fusion.2", 700, 100),
        _ev(tr.OPS_LINE, "fusion.2", 950, 100),        # runs past the window
    ]


def test_busy_idle_and_ops():
    red = tr.reduce(_trace())
    assert red["window_ns"] == 1000
    # busy: [100, 300] + [350, 400] + [700, 800] + [950, 1000]
    assert red["busy_ns"] == 200 + 50 + 100 + 50
    assert red["ops"] == {"fusion.1": 150, "custom-call.7": 150,
                          "fusion.2": 150}
    assert red["modules"] == {"jit_step": [(100, 300)]}
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(150e-9)


def test_ops_are_labelled_by_program_and_instruction():
    hlo = "%while.12 = (s32[], bf16[2,1,2048]) while((s32[]) %tuple.61)"
    ev = _ev(tr.OPS_LINE, hlo, 100, 50, hlo_module="jit_step")
    assert tr.op_label(ev) == "jit_step/while.12"
    red = tr.reduce(_trace() + [ev])
    assert red["ops"]["jit_step/while.12"] == 50


def test_idle_gaps_go_to_the_innermost_span():
    red = tr.reduce(_trace())
    # idle: [0,100] mid 50 -> step_batch (50..150, shortest);
    # [300,350] -> tick 1; [400,700] mid 550 -> outside (between ticks);
    # [800,950] mid 875 -> tick 2
    assert red["idle_by_span"] == {"bench.step_batch": 100,
                                   "outside": 300, "bench.tick": 200}
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["outside", pytest.approx(300e-9)]
    assert len(gaps) <= 10


def test_device_time_inside_spans():
    red = tr.reduce(_trace())
    assert tr.device_time_ns(red, "bench.tick") == [250, 150]


def test_window_argument_and_missing_device():
    red = tr.reduce(_trace(), window=(0, 400))
    assert red["busy_ns"] == 250 and red["window_ns"] == 400
    with pytest.raises(ValueError):
        tr.reduce([e for e in _trace() if e["plane"] != DEV])


def test_union_and_overlap():
    merged = tr.union_ns([(5, 7), (0, 2), (1, 3), (7, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert tr.overlap_ns(merged, 2, 6) == 2
