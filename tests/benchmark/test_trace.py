"""The trace reduction: interval arithmetic on hand-made events, and the
whole reduction on a small recorded chip trace (TPU v5e, PR 24)."""

import os

import pytest

from harness import trace as T

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def test_union_and_subtract():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.total(T.union([(0, 2), (1, 3)])) == 3
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                       (7, 10)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T.subtract([(0, 4)], []) == [(0, 4)]
    assert T.subtract([(1, 2)], [(0, 5)]) == []


def hand_trace():
    ops0 = [("fusion.1", 100, 50), ("all-reduce.1", 150, 100),
            ("fusion.2", 200, 100), ("all-reduce.2", 400, 50)]
    ops1 = [("fusion.1", 100, 100)]
    mods = [("jit_train_step(1)", 100, 200), ("jit_eval(2)", 400, 50)]
    # an asynchronous collective: its start-to-done span overlaps fusion.2
    # on 250..300 and is alone on 300..320
    async0 = [("all-reduce-start.3", 250, 70)]
    return T.Trace({0: {"ops": ops0, "async": async0, "modules": mods},
                    1: {"ops": ops1, "async": [], "modules": []}},
                   [("bench.train_step_call", 0, 90)], 0, 1000)


def test_busy_idle_modules_collectives_on_hand_made_events():
    tr = hand_trace()
    # device 0 busy 100..300 and 400..450 = 250; device 1 busy 100
    assert T.busy_s(tr) == pytest.approx((250 + 100) / 2 / 1e9)
    assert T.idle_share(tr) == pytest.approx(100 * (1 - 175 / 1000))
    assert T.module_calls(tr, lambda n: n.startswith("jit_train_step")) \
        == [pytest.approx(200 / 1e9)]
    # all-reduce.1 is alone on 150..200, all-reduce.2 on 400..450, the
    # asynchronous one on 300..320; device 1 has no collective and counts
    # as 0 in the mean over devices
    assert T.exposed_collective_s(tr) == pytest.approx(120 / 2 / 1e9)
    assert T.op_time(tr, T.is_collective) == (pytest.approx(150 / 1e9), 2)
    assert T.top_ops(tr, 1)[0][0] == "fusion.1"
    gaps = dict(T.idle_gaps(tr))
    # 0..100 has its midpoint inside the harness span; the rest does not
    assert gaps["bench.train_step_call"] == pytest.approx(100 / 1e9)
    assert gaps["outside harness spans"] == pytest.approx(650 / 1e9)


def test_no_collective_gives_nothing_to_read():
    tr = hand_trace()
    tr.devices[0]["ops"] = [o for o in tr.devices[0]["ops"]
                            if not T.is_collective(o[0])]
    tr.devices[0]["async"] = []
    assert T.exposed_collective_s(tr) is None


def test_json_round_trip(tmp_path):
    tr = hand_trace()
    p = str(tmp_path / "t.json.gz")
    T.dump_json(tr, p)
    back = T.load_json(p)
    assert back.devices == tr.devices and back.host == tr.host
    assert (back.start_ns, back.end_ns) == (tr.start_ns, tr.end_ns)


# ---- the recorded chip trace: the first two train steps of a capture of
# gpt2m-train-dp1 on one TPU v5e (chiprun, PR 24), reduced by load_xplane
# and cut by tools/trace_look.py.

@pytest.fixture(scope="module")
def chip_trace():
    return T.load_json(os.path.join(FIXTURES, "train_v5e.json.gz"))


class _Ctx:
    def __init__(self, trace):
        from harness import peaks
        self.trace = trace
        self.peaks = peaks.peaks_for("TPU v5 lite")
        self.sizes = {"n_head": 16, "n_embd": 1024}
        self.shape = {"rows_per_chip": 8, "seq_len": 1024}
        self.chips = 1
        self.notes = []
        self.say = self.notes.append


def test_chip_trace_planes_and_modules(chip_trace):
    assert sorted(chip_trace.devices) == [0]
    mods = chip_trace.devices[0]["modules"]
    assert len(mods) >= 2
    assert all(m[0].startswith("jit_train_step(") for m in mods)
    assert len(chip_trace.devices[0]["ops"]) == 16000


def test_chip_trace_step_time_and_idle_share(chip_trace):
    from harness.loader import load_reader
    ctx = _Ctx(chip_trace)
    step_ms = load_reader("train.step_device_ms")(ctx)
    assert 150 < step_ms < 200           # 172.8 ms a step, my chip run PR 24
    calls = T.module_calls(chip_trace,
                           lambda n: n.startswith("jit_train_step"))
    assert max(calls) - min(calls) < 0.002
    idle = load_reader("train.device_idle_share")(ctx)
    assert 0 <= idle < 3
    assert T.busy_s(chip_trace) <= chip_trace.window_s
    # one chip: no collective in the trace, nothing to read
    assert T.exposed_collective_s(chip_trace) is None
    assert load_reader("train.exposed_collective_ms")(ctx) is None


def test_chip_trace_flash_kernels_and_roofline(chip_trace):
    from harness.loader import load_reader
    read = load_reader("train.flash_roofline_share")
    kind_of = read.__globals__["kind_of"]
    kinds = [kind_of(n) for n, _, _ in chip_trace.devices[0]["ops"]]
    # two whole steps of 24 layers: 48 calls of each kernel
    assert kinds.count("fwd") >= 48 and kinds.count("dq") >= 48
    assert kinds.count("dkv") >= 48
    ctx = _Ctx(chip_trace)
    share = read(ctx)
    assert 10 < share < 100
    assert "bound by compute" in ctx.notes[0]


def test_short_name_of_a_recorded_op():
    full = ('%attn.97 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, '
            'bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}) custom-call('
            'bf16[128,1024,64]{2,1,0:T(8,128)(2,1)S(1)} %bitcast.3559, '
            'f32[128,1024,8]{2,1,0:T(8,128)} %pallas_call.239), '
            'custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={bf16[128,1024,64]{2,1,0}}')
    assert T.short_name(full) == ("%attn.97 tpu_custom_call "
                                  "(bf16[128,1024,64], bf16[128,1024,64])")
    assert T.short_name(
        "%fusion.12 = (f32[50257,1024]{1,0:T(8,128)}, f32[50257,1024]"
        "{1,0}) fusion(f32[50257,1024]{1,0} %p), kind=kLoop") == "%fusion.12"
    assert T.short_name("%all-reduce.5 = f32[1024]{0} all-reduce(f32[1024]"
                        "{0} %x), replica_groups={}") == "%all-reduce.5"
    assert T.is_collective("%all-reduce.5")
    assert T.is_collective("%all-reduce-start.2")
    assert not T.is_collective("%fusion.12")


# ---- one step of gpt2m-train-dp4 on the four chips of a 2x2 v5e host
# (chiprun --chips 4, PR 24): the first 8000 ops of every device.

@pytest.fixture(scope="module")
def dp4_trace():
    return T.load_json(os.path.join(FIXTURES, "train_dp4_v5e.json.gz"))


def test_dp4_trace_collectives_are_exposed(dp4_trace):
    from harness.loader import load_reader
    assert sorted(dp4_trace.devices) == [0, 1, 2, 3]
    for d in dp4_trace.devices.values():
        assert len(d["modules"]) == 1
        # nine synchronous all-reduces a step, none started asynchronously
        assert sum(T.is_collective(n) for n, _, _ in d["ops"]) == 9
        assert not any(T.is_collective(n) for n, _, _ in d["async"])
    ctx = _Ctx(dp4_trace)
    ctx.chips = 4
    exposed = load_reader("train.exposed_collective_ms")(ctx)
    # a synchronous collective is alone on its device: all of it is exposed
    per_device = [sum(dur for n, _, dur in d["ops"] if T.is_collective(n))
                  for d in dp4_trace.devices.values()]
    assert exposed == pytest.approx(sum(per_device) / 4 / 1e6, rel=1e-6)
    assert 14 < exposed < 18             # 16.0 ms, my chip run PR 24
    step_ms = load_reader("train.step_device_ms")(ctx)
    assert 190 < step_ms < 195           # 172.8 ms on one chip
    assert 0 <= T.idle_share(dp4_trace) < 3
