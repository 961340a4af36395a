"""The readers of the scheduler's step spans on a synthetic run: prefill
time, padded prefill work, a decode step's host time and the pool's peak
use, each over the window only, and None where the program recorded no
such span (an untraced run, or a program whose spans lack the arguments)."""
import pytest

from benchmarks.chip import harness, manifest

SERVER = {"n_slots": 4, "n_blocks": 0, "max_len": 100, "block_size": 10}


def run_of(spans, server=SERVER):
    return harness.Run(spec={}, traffic={}, server=server, seconds=10.0, window=(10.0, 20.0),
                       requests=[], steps=[], spans=spans, setup_s=1.0, tail_prefill=False,
                       peak={})


def read(name, run):
    return manifest.load_reader(name)(run)


NAMES = ("step.prefill_ms", "step.prefill_pad_frac", "step.host_ms", "pool.live_peak_frac")

# three steps: one before the window, one that admits and decodes, one that
# runs a chunk beside its decode; the last admission starts after the close
SPANS = [
    ("step", 9.0, 0.5, {"step": 1, "blocks_live": 39}),
    ("admit", 9.1, 0.3, {"step": 1, "req": 0, "prompt": 100, "bucket": 128, "start": 0}),
    ("decode", 9.4, 0.05, {"step": 1}),
    ("step", 11.0, 0.5, {"step": 2, "blocks_live": 12}),
    ("grow", 11.0, 0.01, {"step": 2, "new_blocks": 1}),
    ("admit", 11.02, 0.2, {"step": 2, "req": 1, "prompt": 40, "bucket": 32, "start": 10}),
    ("decode", 11.25, 0.2, {"step": 2}),
    ("emit", 11.46, 0.02, {"step": 2, "tokens": 3, "finished": 0}),
    ("step", 12.0, 0.4, {"step": 3, "blocks_live": 14}),
    ("chunk", 12.01, 0.01, {"step": 3, "req": 2, "done": 0, "tail": 50, "bucket": 64}),
    ("decode", 12.05, 0.3, {"step": 3}),
    ("step", 13.0, 0.1, {"step": 4, "blocks_live": 14}),  # nothing decoded
    ("admit", 13.0, 0.1, {"step": 4, "req": 3, "prompt": 8, "bucket": 8, "start": 0}),
    ("admit", 20.5, 0.4, {"step": 9, "req": 4, "prompt": 8, "bucket": 8, "start": 0}),
]


def test_prefill_ms_is_the_mean_admit_span_in_the_window():
    assert read("step.prefill_ms", run_of(SPANS)) == pytest.approx((0.2 + 0.1) / 2 * 1e3)


def test_prefill_pad_frac_counts_real_tail_tokens_against_buckets():
    real, padded = (40 - 10) + 50 + 8, 32 + 64 + 8
    got = read("step.prefill_pad_frac", run_of(SPANS))
    assert got == pytest.approx(100 * (1 - real / padded))


def test_host_ms_leaves_out_device_bound_children_and_steps_without_decode():
    step2 = 0.5 - 0.2 - 0.2  # grow and emit stay in the host's time
    step3 = 0.4 - 0.01 - 0.3
    assert read("step.host_ms", run_of(SPANS)) == pytest.approx((step2 + step3) / 2 * 1e3)


def test_host_ms_matches_children_by_step_and_time():
    # a drained step() call repeats the number of the step after it: the
    # children belong to the span they start in
    spans = [("step", 11.0, 0.1, {"step": 5, "blocks_live": 0}),
             ("step", 12.0, 0.5, {"step": 5, "blocks_live": 3}),
             ("admit", 12.1, 0.2, {"step": 5, "req": 0, "prompt": 4, "bucket": 4, "start": 0}),
             ("decode", 12.3, 0.1, {"step": 5})]
    assert read("step.host_ms", run_of(spans)) == pytest.approx(0.2 * 1e3)


def test_live_peak_frac_over_the_pool():
    # dense-equivalent pool: 4 slots x 10 blocks; the 39 before the window
    # does not count
    assert read("pool.live_peak_frac", run_of(SPANS)) == pytest.approx(100 * 14 / 40)
    sized = dict(SERVER, n_blocks=28)
    assert read("pool.live_peak_frac", run_of(SPANS, sized)) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NAMES)
def test_untraced_run_reads_none(name):
    assert read(name, run_of([])) is None


@pytest.mark.parametrize("name", NAMES)
def test_spans_without_the_new_arguments_read_none(name):
    """A program whose admit spans end at the dispatch records neither a
    ``bucket`` nor a ``step`` span: the readers read nothing there."""
    spans = [("admit", 11.0, 0.01, {"step": 2, "req": 0, "slot": 0, "prompt": 40}),
             ("chunk", 11.5, 0.01, {"step": 3, "req": 1, "slot": 1, "done": 0, "tail": 8}),
             ("decode", 12.0, 0.2, {"step": 3, "n_decode": 2, "n_live": 2})]
    assert read(name, run_of(spans)) is None
