"""The reduction from a profiler capture to metrics, on cut-down
pieces of two real TPU v5 lite captures of this repo's programs (taken
by PR 24's builder: 0.25 s of gpt3-1.3b decoding, 0.7 s of ERNIE-base
pre-training), and on small hand-made captures."""

import gzip
import json
import os

import pytest

from benchmarks import xplane

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _load(name):
    with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def decode():
    return _load("decode_cut.json.gz")


@pytest.fixture(scope="module")
def pretrain():
    return _load("pretrain_cut.json.gz")


def test_decode_busy_and_idle(decode):
    summary = xplane.device_summary(decode)
    assert summary["window_s"] == pytest.approx(0.25)
    assert summary["busy_s"] == pytest.approx(0.228506454, rel=1e-6)
    assert xplane.idle_share_percent(decode) == pytest.approx(8.597, abs=1e-3)


def test_decode_step_module(decode):
    assert xplane.main_module_median_s(decode) == pytest.approx(
        0.075057, rel=1e-4)


def test_decode_op_groups_are_short_and_merged(decode):
    groups = xplane.op_groups(decode)
    assert groups[0][0] == "copy bf16[1281,16,16,128] x96"
    assert groups[1][0] == "copy bf16[16,16,128,16,128] x48"
    assert all(len(label) <= 80 for label, _ in groups)
    assert [s for _, s in groups] == sorted((s for _, s in groups),
                                            reverse=True)
    assert len(groups) <= 10
    # no Mosaic kernel in the serving step
    assert xplane.op_seconds(decode, xplane.is_mosaic) == 0.0


def test_decode_idle_gaps_name_the_hosts_work(decode):
    gaps = dict(xplane.idle_gaps(decode, "serving.step", top=100))
    assert "bench.wait" not in gaps
    assert gaps["np.asarray(jax.Array)"] > 0.005
    assert gaps["step.sample"] > 0.001
    assert "unattributed" in gaps
    idle = xplane.device_summary(decode)
    assert sum(gaps.values()) == pytest.approx(
        idle["window_s"] - idle["busy_s"], rel=1e-6)


def test_pretrain_mosaic_share_and_step(pretrain):
    summary = xplane.device_summary(pretrain)
    mosaic = xplane.op_seconds(pretrain, xplane.is_mosaic)
    assert 100 * mosaic / summary["busy_s"] == pytest.approx(23.33, abs=0.01)
    assert xplane.main_module_median_s(pretrain) == pytest.approx(
        0.33223, rel=1e-4)
    labels = [label for label, _ in xplane.op_groups(pretrain)]
    assert any(label.startswith("tpu_custom_call ") for label in labels)
    assert xplane.idle_share_percent(pretrain) < 1.0


def test_pretrain_gaps_without_a_driver_span_are_unattributed(pretrain):
    gaps = dict(xplane.idle_gaps(pretrain, "no.such.span"))
    assert set(gaps) <= {"unattributed", "gaps under 20 us"}


@pytest.mark.parametrize("hlo,want", [
    ("%copy.422 = bf16[16,16,128,16,128]{4,3,2,1,0:T(8,128)(2,1)} "
     "copy(bf16[16,16,128,16,128]{4,1,3,2,0:T(8,128)(2,1)} %bitcast.35)",
     "copy bf16[16,16,128,16,128]"),
    ("%fusion.2765 = f32[16,50304]{1,0:T(8,128)} fusion(bf16[16,2048]{1,0} "
     "%fusion.49), kind=kOutput, calls=%fused_computation",
     "fusion f32[16,50304]"),
    ("%jvp__.13 = (bf16[768,512,64]{2,1,0:T(8,128)(2,1)}, f32[768,512,8]"
     "{2,1,0:T(8,128)}) custom-call(bf16[768,512,64]{2,1,0} %x), "
     "custom_call_target=\"tpu_custom_call\", operand_layout_constraints={}",
     "tpu_custom_call (bf16[768,512,64], f32[768,512,8])"),
    ("jit_step_fn(3024791988818642734)", "jit_step_fn(3024791988818642734)"),
    ("%x = f32[2]{0} add(" + "f32[2]{0} %a, " * 40 + ")", "add f32[2]"),
])
def test_op_label(hlo, want):
    assert xplane.op_label(hlo) == want
    assert len(xplane.op_label(hlo * 3)) <= 80


def _capture(ops, host=(), window=(0, 1000)):
    names, index = [], {}

    def nid(n):
        if n not in index:
            index[n] = len(names)
            names.append(n)
        return index[n]
    return {
        "names": names,
        "devices": [{"name": "/device:TPU:0", "modules": [],
                     "ops": [[nid(n), s, d] for n, s, d in ops]}],
        "host": [
            {"name": "driver", "events": [[nid(n), s, d] for n, s, d in host]},
            {"name": "tracer", "events": [[nid(xplane.WINDOW_SPAN),
                                           window[0], window[1] - window[0]]]},
        ]}


def test_busy_is_the_union_clipped_to_the_window():
    cap = _capture([("%a = f32[1] add()", -50, 100),      # clipped to 50
                    ("%b = f32[1] add()", 100, 200),
                    ("%c = f32[1] add()", 250, 100),      # overlaps %b
                    ("%d = f32[1] add()", 900, 500)],     # clipped to 100
                   window=(0, 1000))
    summary = xplane.device_summary(cap)
    assert summary["busy_s"] * 1e9 == pytest.approx(50 + 250 + 100)
    assert summary["window_s"] * 1e9 == pytest.approx(1000)
    assert xplane.idle_share_percent(cap) == pytest.approx(60.0)


def test_gaps_go_to_the_innermost_span():
    ms = 1_000_000
    cap = _capture(
        [("%a = f32[1] add()", 0, 10 * ms), ("%b = f32[1] add()", 30 * ms,
                                             10 * ms)],
        host=[("outer", 8 * ms, 20 * ms),        # 8..28
              ("inner", 12 * ms, 6 * ms),        # 12..18, inside outer
              ("marker", 0, 1)],
        window=(0, 40 * ms))
    gaps = dict(xplane.idle_gaps(cap, "marker"))
    assert gaps["inner"] * 1e3 == pytest.approx(6.0)
    assert gaps["outer"] * 1e3 == pytest.approx(12.0)     # 10..12, 18..28
    assert gaps["unattributed"] * 1e3 == pytest.approx(2.0)   # 28..30


def test_nothing_on_the_device_is_nothing_to_read():
    cap = _capture([])
    assert xplane.device_summary(cap) is None
    assert xplane.idle_share_percent(cap) is None
    assert xplane.idle_share_percent(None) is None
    assert xplane.main_module_median_s(cap) is None
