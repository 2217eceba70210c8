"""The reduction from a profiler trace to numbers, on a small trace
recorded on the v5e (fixtures/fixture.xplane.pb: five rounds of one jitted
program of four 1024x1024 bf16 matmul+tanh fusions inside `bench.decode`,
each followed by a 2 ms host sleep inside `bench.prefill`, the whole
inside `bench.window`), and the kernels' work by hand."""

import os

import pytest

from benchmarks import kernel_work, trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "fixture.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(tr.load(FIXTURE))


class _Named:
    def __init__(self, name, **fields):
        self.name = name
        self.__dict__.update(fields)


def _profile(planes):
    """A profile by hand: {plane: {line: [(start_ns, end_ns, name)]}} as
    `jax.profiler.ProfileData` shows one."""
    return _Named("profile", planes=[
        _Named(plane, lines=[
            _Named(line, events=[
                _Named(name, start_ns=s, duration_ns=e - s)
                for s, e, name in events])
            for line, events in lines.items()])
        for plane, lines in planes.items()])


def test_busy_and_idle_share_of_the_known_trace(reduced):
    assert reduced["chips"] == 1
    # 20 fusions of ~10 us each inside a window of five 2 ms sleeps
    assert reduced["window_s"] == pytest.approx(0.016279629, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.000201911, rel=1e-6)
    idle_share = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0.98 < idle_share < 0.99
    assert reduced["kernel_s"] == 0 and reduced["collective_exposed_s"] == 0


def test_per_operation_time_under_stable_names(reduced):
    ops = reduced["ops"]
    name = "convolution_tanh_fusion_bf16_1024_1024_"
    assert ops[name] == pytest.approx(0.000201849, rel=1e-6)
    assert reduced["breakdown"]["device_ops"][0] == [name, ops[name]]
    assert all(len(row) == 2 for row in reduced["breakdown"]["device_ops"])
    assert len(reduced["breakdown"]["device_ops"]) <= 10


def test_idle_gaps_go_to_the_host_span_open_then(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    # the device waits while the host sleeps inside bench.prefill
    assert max(gaps, key=gaps.get) == "bench.prefill"
    assert gaps["bench.prefill"] > 0.8 * (
        reduced["window_s"] - reduced["busy_s"])
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_kernels_by_name_sum_to_kernel_s(reduced):
    # the recorded trace has fusions only: no kernel, and nothing made up
    assert reduced["kernels"] == {} and reduced["kernel_s"] == 0
    # two kernels on two chips, one of them in two calls; a fusion beside
    kernel = ("%{0}.{1} = bf16[{2},128]{{1,0}} custom-call(bf16[8] %x), "
              'custom_call_target="tpu_custom_call"')
    ops = [(0, 300, kernel.format("paged", 1, 32)),
           (400, 500, kernel.format("paged", 2, 32)),
           (500, 1000, kernel.format("grouped", 7, 64)),
           (1000, 1500, "%fusion.3 = bf16[8]{0} fusion(bf16[8] %p), "
                        "kind=kLoop")]
    profile = _profile({"/device:TPU:0": {"XLA Ops": ops},
                        "/device:TPU:1": {"XLA Ops": ops[1:]},
                        "/host:CPU": {"main": [(0, 1500, "bench.window")]}})
    out = tr.reduce(profile)
    assert out["kernels"] == pytest.approx({
        "paged_bf16_32_128_": (400 + 100) / 2 / 1e9,
        "grouped_bf16_64_128_": (500 + 500) / 2 / 1e9})
    assert sum(out["kernels"].values()) == pytest.approx(out["kernel_s"])
    assert out["kernel_s"] == pytest.approx(0.75e-6)
    assert "fusion_bf16_8_" in out["ops"] and out["chips"] == 2


def test_idle_gaps_take_the_phase_on_the_bench_spans_own_thread():
    """Two host threads: the batcher's, with the benchmark's span and the
    program's phases inside it, and a caller's, whose events start later
    and are nothing the device waits for."""
    busy = "%fusion.1 = bf16[8]{0} fusion(bf16[8] %p), kind=kLoop"
    profile = _profile({
        "/device:TPU:0": {"XLA Ops": [(0, 100, busy), (400, 500, busy),
                                      (800, 1000, busy)]},
        "/host:CPU": {
            "batcher": [(0, 1000, "bench.window"), (50, 450, "bench.decode"),
                        (60, 440, "serve.step.fetch"),
                        (70, 430, "np.asarray(jax.Array)"),
                        (460, 990, "serve.loop.admit"),
                        (470, 980, "PjitFunction(prefill)")],
            "caller-3": [(90, 700, "$threading.py:wait")]}})
    gaps = dict(tr.reduce(profile)["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({
        # the gap at 100..400 opens inside bench.decode: the caller's wait
        # started later than the phase and is on another thread, and the
        # runtime's copy inside the phase says less than the phase
        "bench.decode___serve.step.fetch": 300e-9,
        # at 500..800 no span of the benchmark's is open: the program's
        # phase names the gap, not the runtime's call inside it nor the
        # caller's wait
        "no_bench_span___serve.loop.admit": 300e-9})


def test_no_device_plane_reads_as_nothing():
    class Empty:
        planes = []

    assert tr.reduce(Empty()) is None


def test_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total(tr.union([(0, 2), (1, 3)])) == 3
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


@pytest.mark.parametrize("text,stem,result,opcode", [
    ("%fusion.3907 = (bf16[8,1024]{1,0:T(8,128)(2,1)S(1)}, bf16[8,1024,50257]"
     "{1,2,0}) fusion(bf16[50257,1024]{1,0} %custom-call.86), kind=kOutput",
     "fusion", "bf16[8,1024]", "fusion"),
    ("%custom-call.112 = bf16[8,1024,1024]{1,2,0} custom-call(bf16[8] %x), "
     'custom_call_target="tpu_custom_call"',
     "custom-call", "bf16[8,1024,1024]", "custom-call"),
    ("%all-gather-done.5 = f32[4,8]{1,0} all-gather-done((f32[1,8], f32[4,8])"
     " %all-gather-start.5)", "all-gather-done", "f32[4,8]",
     "all-gather-done"),
    ("%copy.7 = bf16[36,1281,16,20,64]{4,3,2,1,0} copy(bf16[36,1281,16,20,64]"
     " %p)", "copy", "bf16[36,1281,16,20,64]", "copy"),
    ("PjitFunction(step)", "PjitFunction(step)", "", ""),
])
def test_instruction_text_is_parsed(text, stem, result, opcode):
    assert tr.parse_instruction(text) == (stem, result, opcode)


def test_kernels_and_collectives_are_told_by_opcode_not_by_operand():
    fusion_on_kernel_output = (
        "%fusion.1 = bf16[8,1024]{1,0} fusion(bf16[8] %custom-call.86, "
        "f32[4] %all-gather-done.2), kind=kLoop")
    assert not tr.is_kernel(fusion_on_kernel_output)
    assert not tr.is_collective(fusion_on_kernel_output)
    assert tr.is_kernel("%custom-call.1 = bf16[32,20,1,64]{3,2,1,0} "
                        "custom-call(bf16[1] %q)")
    assert tr.is_collective("%reduce-scatter.3 = f32[8]{0} "
                            "reduce-scatter(f32[32] %g)")
    assert tr.stable_name("%copy.7 = bf16[36,1281,16,20,64]{4,3,2,1,0} "
                          "copy(bf16[1] %p)") == "copy_bf16_36_1281_16_20_64_"


def test_flash_work_by_hand():
    # [B=8, S=1024, H=16, Dh=64], causal, forward + backward, bf16:
    # one unmasked matmul is 2*8*16*1024*1024*64 = 17,179,869,184 FLOPs,
    # halved by the mask; six of them (QK^T, PV, dV, dP, dQ, dK).
    work = kernel_work.flash_attention_work(8, 1024, 16, 64)
    assert work["flops"] == 6 * 8_589_934_592 == 51_539_607_552
    # twelve arrays of 8*1024*16*64 two-byte elements cross HBM
    assert work["bytes"] == 12 * 8_388_608 * 2 == 201_326_592
    fwd = kernel_work.flash_attention_work(8, 1024, 16, 64, backward=False)
    assert fwd == {"flops": 2 * 8_589_934_592, "bytes": 4 * 8_388_608 * 2}
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # compute-bound, just: 261.6 us of matmuls against 245.8 us of traffic
    assert kernel_work.floor_seconds(work, peak) == pytest.approx(
        51_539_607_552 / 197e12)


def test_paged_decode_work_by_hand():
    # 32 slots, each with 100 tokens of context, 20 heads of 64, bf16:
    # per slot K and V of 100 tokens + q + out = 202 * 1280 elements,
    # and two matmuls of 2*100*1280 FLOPs.
    work = kernel_work.paged_decode_work([100] * 32, 20, 64)
    assert work["bytes"] == 32 * 202 * 1280 * 2 == 16_547_840
    assert work["flops"] == 32 * 4 * 100 * 1280 == 16_384_000
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # bandwidth-bound by a factor of ~240
    assert kernel_work.floor_seconds(work, peak) == pytest.approx(
        16_547_840 / 819e9)
    assert kernel_work.train_flops_per_token(1000, 2, 8, 16) == \
        6000 + 12 * 2 * 8 * 16
    # four K/V heads under the 20 query heads: the same matmuls over a
    # fifth of the keys and values; q and the output are as wide as before
    grouped = kernel_work.paged_decode_work([100] * 32, 20, 64, kv_heads=4)
    assert grouped["flops"] == work["flops"]
    assert grouped["bytes"] == 32 * (200 * 4 + 2 * 20) * 64 * 2 < work["bytes"]
    assert kernel_work.paged_decode_work([100] * 32, 20, 64, kv_heads=20) \
        == work
    flash = kernel_work.flash_attention_work(8, 1024, 16, 64, kv_heads=4)
    assert flash["flops"] == 51_539_607_552
    assert flash["bytes"] == 6 * 8 * 1024 * (16 + 4) * 64 * 2
