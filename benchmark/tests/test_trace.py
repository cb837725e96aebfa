"""The trace reduction on a small recorded trace kept beside this file:
``data/trace_excerpt.json.gz`` holds the device lines of one decode step and
one prefill of ``gpt2-large`` at 8 slots (the former chat mix) on one TPU v5 lite (PR 24's chip run), as
``benchlib.trace.excerpt`` wrote them, with the run of the sync program."""

from types import SimpleNamespace

import pytest

from benchlib import flops, manifest, peaks, trace

RECORDED = manifest.BENCH / "tests" / "data" / "trace_excerpt.json.gz"
#: The recorded step's page-gather kernel (``ops/ragged_decode._gather_pages_pallas``,
#: which the program no longer has): a Mosaic call whose result is the gathered
#: view ``[entries, page_size, n_embd]``.
PAGE_GATHER = SimpleNamespace(EVENTS=r"= \w+\[\d+,\d+,\d+\]\S* custom-call\(.*tpu_custom_call")


@pytest.fixture(scope="module")
def recorded():
    planes = trace.read_planes(str(RECORDED))
    lines = dict(next(lines for name, lines in planes if name.startswith("/device:")))
    ops = lines[trace.OP_LINE]
    # the host saw the sync program end at perf_counter 100.0 in this test's clock
    sync_end = next(s + d for n, s, d, _ in lines[trace.MODULE_LINE] if n.startswith(trace.SYNC_NAME))
    to_perf = lambda ns: 100.0 + (ns - sync_end) * 1e-9
    both = ops + [m for m in lines[trace.MODULE_LINE] if not m[0].startswith(trace.SYNC_NAME)]
    first, last = min(e[1] for e in both) - 1e3, max(e[1] + e[2] for e in both) + 1e3
    return trace.load(str(RECORDED), 100.0, to_perf(first), to_perf(last))


def test_busy_and_idle_share(recorded):
    assert len(recorded.devices) == 1
    busy, window = recorded.busy_s(), recorded.window_s
    assert 0.0 < busy <= window
    gaps = recorded.gaps()
    assert sum(e - s for s, e in gaps) == pytest.approx(window - busy, rel=1e-6)
    # one step, a gap in which the host admits a request, one prefill
    assert 0.5 < busy / window < 1.0
    assert recorded.module_runs("jit_step") and recorded.module_runs("jit_prefill")
    assert 0.03 < recorded.module_runs("jit_step")[0] < 0.2


def test_top_ops_group_by_operation_and_result_shape(recorded):
    top = recorded.top_ops(5)
    assert top and top[0][1] >= top[-1][1] > 0
    assert all(" " in name or name.isidentifier() for name, _ in top)
    assert sum(s for _, s in recorded.top_ops(10_000)) >= recorded.busy_s() * 0.999


def test_kernel_time_and_a_roofline_under_100(recorded):
    kernel = PAGE_GATHER
    seconds, events = recorded.op_seconds(kernel.EVENTS)
    assert events == 72                         # K and V in each of 36 layers, one step
    assert 0 < seconds < recorded.busy_s()
    assert 5e-6 < seconds / events < 50e-6      # about 12 us a call
    cfg = manifest.read_json(manifest.BENCH / "configs" / "gpt2-large.json")
    chip = peaks.peaks("TPU v5 lite")
    # eight residents at about 50 cached positions each, one token each from that step
    records = [{"prompt": [0] * 48, "token_t": [recorded.t0 - 1.0, recorded.t0 + 1e-3]}
               for _ in range(8)]
    ctx = SimpleNamespace(config=cfg, records=records, trace=recorded, chips=1, peaks=chip,
                          kernels={"page_gather": kernel})
    per_call = manifest.plugin("readers", "kernel_us_per_call").read(ctx, kernel="page_gather")
    assert per_call == pytest.approx(1e6 * seconds / events)
    # the whole step against the chip: the blocks and the head once (the
    # embedding tables are not read whole), the residents' K and V once
    step = recorded.module_runs("jit_step")[0]
    share = manifest.plugin("readers", "step_hbm_roofline").read(ctx, pattern="jit_step")
    needed = 2 * flops.lm_step_params(50257, 36, 1280, 5120) + 8 * 49 * 184320 + 8 * 2 * 1280 * 2
    assert share == pytest.approx(100.0 * needed / 819e9 / step)
    assert 2 * 708e6 < 2 * flops.lm_step_params(50257, 36, 1280, 5120) < 1.56e9   # not the 1.68 GB of all weights
    assert 0 < share < 100.0
    mfu = manifest.plugin("readers", "step_mfu").read(ctx, pattern="jit_step")
    assert 0 < mfu < share


def test_idle_gaps_go_to_the_innermost_open_span(recorded):
    t0, t1 = recorded.t0, recorded.t1
    spans = [{"name": "outer", "t0": t0 - 1, "t1": t1 + 1},
             {"name": "inner", "t0": t0 - 0.5, "t1": t1 + 0.5}]
    charged = dict(recorded.idle_by_span(spans))
    assert set(charged) == {"inner"}
    assert charged["inner"] == pytest.approx(recorded.window_s - recorded.busy_s(), rel=1e-6)
    assert dict(recorded.idle_by_span([])).keys() == {"(no span)"}


def test_unknown_chip_has_no_peak():
    with pytest.raises(SystemExit):
        peaks.peaks("TPU v9 imaginary")
