"""``flops_flash_backward.py`` against hand counts at both LM cells' shapes,
and the ``flash_backward_roofline`` reader on hand-made event lists."""
import pytest

from perfbench import common, flops, flops_flash_backward, flops_joyai
from perfbench.readers import flash_backward_roofline as reader

GPT2M = common.load_json(common.named_file("configs", "gpt2-medium"))
JOYAI = common.load_json(common.named_file("configs", "joyai-llm-flash"))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_four_products_by_hand_at_both_cells_shapes():
    # gpt2m_train_s1024: 8 x 16 heads, 1,024 keys, 64 wide throughout
    ops, nbytes = flops_flash_backward.flash_backward_call(
        8, 16, 1024, 1024, 64, 64, 2, causal=True)
    square = 8 * 16 * 1024 * 1024
    assert ops == 2 * square * (64 + 64 + 64 + 64) // 2 == 34_359_738_368
    assert ops == 2 * flops.flash_forward_call(8, 16, 1024, 1024, 64, 2)[0]
    # q, k, v, o, do, dq, dk, dv: eight arrays of 128 x 1,024 x 64
    assert nbytes == 2 * 8 * 128 * 1024 * 64 + 4 * 128 * 1024
    # joyai_flash_train_s8192: 32 heads, 8,192 keys, q/k 192, v/o/do 128
    ops, nbytes = flops_flash_backward.flash_backward_call(
        1, 32, 8192, 8192, 192, 128, 2, causal=True)
    square = 32 * 8192 * 8192
    assert ops == 2 * square * (128 + 128 + 192 + 192) // 2 \
        == 1_374_389_534_720
    assert ops == 2 * flops_joyai.flash_forward_call(
        1, 32, 8192, 8192, 192, 128, 2)[0]
    assert nbytes == 2 * 32 * 8192 * (4 * 192 + 4 * 128) + 4 * 32 * 8192
    # without causal the whole square
    assert flops_flash_backward.flash_backward_call(
        1, 32, 8192, 8192, 192, 128, 2, causal=False)[0] == 2 * ops
    # compute-bound at both: 0.174 ms and 6.98 ms a call at the peak
    for args, want in (((8, 16, 1024, 1024, 64, 64, 2), 0.1744e-3),
                       ((1, 32, 8192, 8192, 192, 128, 2), 6.977e-3)):
        t, bound = flops.roofline_seconds(
            *flops_flash_backward.flash_backward_call(*args), 197e12, 819e9)
        assert bound == "compute" and t == pytest.approx(want, rel=1e-3)


class _Trace(object):
    def __init__(self, events):
        self.events = events

    def first_chip_ops(self):
        return self.events


def _ctx(config, events, batch, seq, peaks=PEAKS):
    return {"trace": _Trace(events), "config": config, "peaks": peaks,
            "chips": 1, "counters": {"steps": 2, "batch": batch, "seq": seq}}


def _least(config, batch, seq):
    heads, d_qk, d_v = reader.widths(config)
    return flops.roofline_seconds(
        *flops_flash_backward.flash_backward_call(batch, heads, seq, seq,
                                                  d_qk, d_v, 2),
        PEAKS["bf16_flops_per_s"], PEAKS["hbm_bytes_per_s"])[0]


def test_widths_from_the_configuration():
    assert reader.widths(GPT2M) == (16, 64, 64)
    assert reader.widths(JOYAI) == (32, 192, 128)


@pytest.mark.parametrize("config,batch,seq", [(GPT2M, 8, 1024),
                                              (JOYAI, 1, 8192)])
def test_one_kernel_a_call(config, batch, seq):
    """Three calls of one kernel, as XLA numbers them, among other events:
    3 x the least time over the three durations."""
    least_ns = _least(config, batch, seq) * 1e9
    events = [("%fusion.7 = f32[8] fusion(...)", 0, 900),
              ("%flash_forward.2 = bf16[8] custom-call(...)", 1000, 500),
              ("%flash_backward = bf16[8] custom-call(...)", 2000,
               int(2 * least_ns)),
              ("%flash_backward.1 = bf16[8] custom-call(...)", 9e9,
               int(4 * least_ns)),
              ("%flash_backward.12 = bf16[8] custom-call(...)", 2e10,
               int(6 * least_ns))]
    found, kernels = reader.backward_events(events)
    assert (len(found), kernels) == (3, 1)
    assert reader.read(_ctx(config, events, batch, seq)) \
        == pytest.approx(100.0 * 3 / 12, rel=1e-4)


def test_two_kernels_a_call_count_once():
    """A call made of a dk/dv kernel and a dq kernel: four events are two
    calls, and the reading is 2 x the least time over all four."""
    least_ns = _least(GPT2M, 8, 1024) * 1e9
    events = [("%flash_backward_dkdv = custom-call(...)", 0,
               int(3 * least_ns)),
              ("%flash_backward_dq = custom-call(...)", 5e9,
               int(2 * least_ns)),
              ("%flash_backward_dkdv.1 = custom-call(...)", 1e10,
               int(3 * least_ns)),
              ("%flash_backward_dq.1 = custom-call(...)", 2e10,
               int(2 * least_ns))]
    found, kernels = reader.backward_events(events)
    assert (len(found), kernels) == (4, 2)
    assert reader.read(_ctx(GPT2M, events, 8, 1024)) \
        == pytest.approx(100.0 * 2 / 10, rel=1e-4)


@pytest.mark.parametrize("events", [
    [],
    [("%fusion.1 = fusion(...)", 0, 10),
     ("%flash_forward.3 = custom-call(...)", 20, 10)],   # the parent's trace
])
def test_nothing_to_read_without_a_backward_kernel(events):
    assert reader.read(_ctx(GPT2M, events, 8, 1024)) is None
    assert reader.read(_ctx(JOYAI, events, 1, 8192)) is None


def test_nothing_to_read_without_peaks_or_a_sequence():
    events = [("%flash_backward = custom-call(...)", 0, 1000)]
    assert reader.read(_ctx(GPT2M, events, 8, 1024, peaks=None)) is None
    ctx = _ctx(GPT2M, events, 8, 1024)
    del ctx["counters"]["seq"]
    assert reader.read(ctx) is None
