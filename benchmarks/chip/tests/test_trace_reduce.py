"""trace_reduce: busy time as a union of intervals, the ``cello_*`` split
by self time, and idle gaps attributed to host spans."""
import pathlib

import pytest

import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _events(device, host):
    return {"devices": {"/device:TPU:0": device}, "host": host}


def test_busy_is_the_union_of_intervals():
    dev = [("cello_stream_Ap0", 10, 30), ("fusion.5", 20, 30),
           ("cello_stream_r0", 70, 10)]
    host = [("bench.window", 0, 100)]
    red = tr.reduce_events(_events(dev, host))
    # [10, 50) and [70, 80): 50 ns busy of 100
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(50e-9)


def test_events_are_clipped_to_the_window():
    dev = [("fusion.1", 0, 40), ("fusion.2", 90, 40)]
    host = [("bench.window", 20, 80)]
    red = tr.reduce_events(_events(dev, host))
    assert red["busy_s"] == pytest.approx(30e-9)      # [20,40) + [90,100)
    assert red["other_s"] == pytest.approx(30e-9)


def test_kernel_split_counts_nested_events_by_self_time():
    # a while loop of 100 ns holding a kernel of 40 ns and a gather of 20
    dev = [("while.3", 0, 100), ("cello_stream_Ap0", 10, 40),
           ("fusion.5", 60, 20)]
    host = [("bench.window", 0, 100)]
    red = tr.reduce_events(_events(dev, host))
    assert red["pallas_s"] == pytest.approx(40e-9)
    assert red["other_s"] == pytest.approx(60e-9)     # 20 gather + 40 loop
    assert red["pallas_s"] + red["other_s"] == pytest.approx(red["busy_s"])
    ops = dict((k, v) for k, v in red["device_ops"])
    assert ops == pytest.approx({"cello_stream_Ap0": 40e-9,
                                 "while.3": 40e-9, "fusion.5": 20e-9})


def test_vmapped_kernels_count_as_kernels():
    assert tr.is_kernel("cello_stream_Ap2.3")
    assert tr.is_kernel("vmap_cello_stream_Ap1_.1")
    assert not tr.is_kernel("fusion.6")
    assert not tr.is_kernel("copy.1")
    dev = [("vmap_cello_stream_Ap1_.1", 0, 30), ("copy.2", 30, 10)]
    red = tr.reduce_events(_events(dev, [("bench.window", 0, 40)]))
    assert red["pallas_s"] == pytest.approx(30e-9)
    assert red["other_s"] == pytest.approx(10e-9)


def test_idle_gaps_go_to_the_host_span_that_overlaps_them_most():
    dev = [("cello_stream_Ap0", 0, 10), ("cello_stream_Ap0", 40, 10),
           ("cello_stream_Ap0", 90, 10)]
    host = [("bench.window", 0, 100),
            ("bench.wait", 0, 12),            # covers 10..12 of gap 1
            ("bench.dispatch", 12, 28),       # covers 12..40: gap 1
            ("serve.batch_build", 50, 45),    # gap 2 (50..90)
            ("serve.dispatch", 85, 15)]       # overlaps gap 2 by 5 only
    red = tr.reduce_events(_events(dev, host))
    gaps = dict((k, v) for k, v in red["idle_gaps"])
    assert gaps == pytest.approx({"bench.dispatch": 30e-9,
                                  "serve.batch_build": 40e-9})


def test_a_gap_with_no_host_span_is_named_so():
    dev = [("fusion.1", 0, 10)]
    host = [("bench.window", 0, 30)]
    red = tr.reduce_events(_events(dev, host))
    assert red["idle_gaps"] == [[tr.NO_SPAN, pytest.approx(20e-9)]]


def test_devices_are_averaged():
    events = {"devices": {"/device:TPU:0": [("fusion.1", 0, 10)],
                          "/device:TPU:1": [("fusion.1", 0, 30)]},
              "host": [("bench.window", 0, 40)]}
    red = tr.reduce_events(events)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(20e-9)


def test_one_window_span_is_required():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_events(_events([("fusion.1", 0, 1)], []))


def _recorded(window: int):
    """The recorded TPU trace (two warm CG solves each of a dense n=1024
    and a sparse n=4096 plan, 4 iterations, one TPU v5e), with one of its
    two ``bench.window`` spans kept."""
    events = tr.load_xplane(str(DATA / "small.xplane.pb"))
    windows = sorted(h for h in events["host"] if h[0] == tr.WINDOW_SPAN)
    assert len(windows) == 2
    drop = windows[1 - window]
    events["host"] = [h for h in events["host"] if h != drop]
    return events


@pytest.mark.parametrize("window", [0, 1])
def test_recorded_trace(window):
    events = _recorded(window)
    assert list(events["devices"]) == ["/device:TPU:0"]
    names = {e[0] for e in events["devices"]["/device:TPU:0"]}
    assert all(not n.startswith("%") and " " not in n for n in names)
    assert any(tr.is_kernel(n) for n in names)
    red = tr.reduce_events(events)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["pallas_s"] > 0 and red["other_s"] > 0
    # self times cover each busy instant exactly once
    assert red["pallas_s"] + red["other_s"] == pytest.approx(red["busy_s"])
    gaps = dict((k, v) for k, v in red["idle_gaps"])
    assert set(gaps) <= {"bench.dispatch", "bench.wait", tr.NO_SPAN}
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert len(red["device_ops"]) == 10
