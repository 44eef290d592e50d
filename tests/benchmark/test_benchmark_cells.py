"""Every kind of cell end to end at a tiny size on the CPU, through the
harness's own functions (Pallas kernels in interpret mode), and the faults
and the control that ``correct`` has to catch. Times printed here are of the
CPU and mean nothing."""

import json
import time

import jax
import numpy as np
import pytest

from bench_tiny import CPU_DEVICE, tiny_cell
from benchmark import check, harness, serve_cell, traffic, train_cell

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def _run(kind, seed=5, seconds=1.0, cell=None):
    runner = train_cell if kind == "train" else serve_cell
    return runner.run(cell or tiny_cell(kind), seed, seconds, False,
                      dict(CPU_DEVICE), time.monotonic(), harness.CompileCounter())


@pytest.mark.parametrize("kind", ["train", "backlog"])
def test_cell_runs_end_to_end_and_prints_the_contracts_line(kind, capsys):
    cell = tiny_cell(kind)
    result = _run(kind, seed=2**31 + 77, cell=cell)
    harness.print_result(**result)
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert list(last) == RESULT_KEYS              # `compared` comes last
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # each number compared, beside its limit, as the last lines on stderr
    tail = captured.err.strip().splitlines()[-len(last["compared"]):]
    assert all(line.startswith("compared ") and " limit " in line for line in tail)


def _broken_step(trainer, fault):
    inner = trainer.step_fn
    if fault == "state_unchanged":
        def step(params, opt_state, guard, x, y, rng, i, scale):
            keep = jax.tree_util.tree_map(lambda a: a + 0, (params, opt_state))
            _, _, guard, metrics = inner(params, opt_state, guard, x, y, rng, i, scale)
            return keep[0], keep[1], guard, metrics
    else:   # half of the batch left out, the mean taken over the rest
        def step(params, opt_state, guard, x, y, rng, i, scale):
            half = x.shape[1] // 2
            return inner(params, opt_state, guard, x[:, :half], y[:, :half],
                         rng, i, scale)
    trainer.step_fn = step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_fault_comes_out_not_correct(fault, monkeypatch):
    built = train_cell.Trainer.__init__

    def broken_init(self, *args, **kwargs):
        built(self, *args, **kwargs)
        _broken_step(self, fault)

    monkeypatch.setattr(train_cell.Trainer, "__init__", broken_init)
    result = _run("train")
    assert result["correct"] is False
    over = {row["name"] for row in result["compared"] if row["ok"] is False}
    assert over & ({"moved_norm_gap"} if fault == "state_unchanged"
                   else {"grad_norm_gap", "grad_diff"})


@pytest.mark.parametrize("fault", ["labels_not_shifted", "row_fed_twice",
                                   "row_not_of_the_shards"])
def test_loader_fault_comes_out_not_correct(fault, monkeypatch):
    """The reference reads its rows from the shard files, so a loader that
    feeds something else is seen, whatever the step then makes of it."""
    from gpt_2_distributed_tpu.data import dataloader

    create = dataloader.create_dataloader

    def broken(*args, **kwargs):
        loader = create(*args, **kwargs)

        class Broken:
            def __iter__(self):
                for x, y in loader:
                    x, y = np.array(x), np.array(y)
                    if fault == "labels_not_shifted":
                        y = x.copy()
                    elif fault == "row_fed_twice":
                        x[1], y[1] = x[0], y[0]
                    else:
                        x[0, 5] = (x[0, 5] + 1) % 257
                    yield x, y

            def close(self):
                loader.close()

        return iter(Broken())

    monkeypatch.setattr(dataloader, "create_dataloader", broken)
    result = _run("train")
    assert result["correct"] is False
    rows = {row["name"]: row for row in result["compared"]}
    assert rows["data_rows_wrong"]["value"] >= 1 and rows["data_rows_wrong"]["ok"] is False


def test_serving_altered_token_comes_out_not_correct(monkeypatch):
    build = serve_cell.build_engine

    def broken_build(cell, seed):
        engine, driver = build(cell, seed)
        inner = engine._decode_fn

        def altered(*args):
            tokens, *rest = inner(*args)
            return (tokens + 1) % engine.config.vocab_size, *rest

        engine._decode_fn = altered
        return engine, driver

    monkeypatch.setattr(serve_cell, "build_engine", broken_build)
    result = _run("backlog")
    assert result["correct"] is False
    assert [row["ok"] for row in result["compared"]] == [False]


def test_training_control_comes_out_not_correct():
    """The reference put in the program's place and computed in fp8, the
    nearest precision below the cell's bfloat16, fails a number; the
    reference against itself fails none."""
    cell = tiny_cell("train")
    trainer = train_cell.Trainer(cell, 3, harness.Spans())
    try:
        observed = train_cell.first_steps(trainer, 3)
    finally:
        trainer.close()
    lr, wd = trainer.lr, trainer.args.weight_decay
    exact = train_cell.reference_numbers(cell, 3, observed, lr, wd)
    rough = train_cell.reference_numbers(cell, 3, observed, lr, wd, control=True)
    leaf_norms = cell["reference"].leaf_norms
    correct, rows = check.judge(
        check.training_numbers(rough, exact, leaf_norms)["numbers"], cell["limits"])
    assert correct is False
    assert "grad_diff" in {r["name"] for r in rows if r["ok"] is False}
    assert check.judge(
        check.training_numbers(exact, exact, leaf_norms)["numbers"], cell["limits"])[0]


def test_serving_control_comes_out_not_correct():
    """At each position of the same prompts and tokens, the token that fp8
    puts first lies further below the reference's best than the limit; the
    program's served tokens stay under it. Twelve layers of width 128: at
    the two-layer size a token's own embedding decides the next token and
    no precision flips it. (CPU readings at this size on 3 seeds: program
    0.0017-0.0027, control 0.044-0.082.)"""
    cell = tiny_cell("backlog", n_layer=12, n_embd=128, vocab_size=2048)
    cell["mix"]["check_tokens"] = 150
    cell["limits"] = {"token_logit_gap": {"limit": 0.01}}
    vocab = cell["sizes"]["vocab_size"]
    engine, driver = serve_cell.build_engine(cell, 4)
    source = traffic.requests(cell["mix"], vocab, 4)
    seen = []
    try:   # a fixed amount of work, not a window: the CPU's speed is no part of it
        for _ in range(2 * cell["mix"]["pool"]):
            request = next(source)
            served = serve_cell.Served(request)
            served.handle = driver.submit(
                request.prompt, request.max_new_tokens, rng=request.index,
                on_token=served.on_token)
            seen.append(served)
        driver.drain()
    finally:
        driver.close()
    finished = [s for s in seen if s.done]
    sample = serve_cell.sample_for_check(cell, finished, 4)
    assert max(sample, key=lambda s: len(s.handle.generated)) is sample[0]
    exact = serve_cell.logit_gaps(cell, 4, sample)
    rough = serve_cell.logit_gaps(cell, 4, sample, control=True)
    assert len(exact) == len(rough) >= cell["mix"]["check_tokens"]
    assert check.judge({"token_logit_gap": exact.max()}, cell["limits"])[0] is True
    assert check.judge({"token_logit_gap": rough.max()}, cell["limits"])[0] is False


@pytest.mark.parametrize("kind", ["train", "backlog"])
def test_calibrate_takes_its_readings_through_the_family(kind):
    """Both paths of the tool that the limits are set with, on the tiny
    cells: the next configuration's limits come from it as committed."""
    from benchmark import calibrate

    cell = tiny_cell(kind)
    what = {"program", "control", "fault"}
    if kind == "train":
        row = calibrate.training(cell, 6, what)
        assert len(row["losses"]) == len(row["reference_losses"]) == 3
        assert check.judge(row["program"], cell["limits"])[0] is True
        assert check.judge(row["control"], cell["limits"])[0] is False
        assert row["fault_half_batch"]["grad_norm_gap"] > 10 * row["program"]["grad_norm_gap"]
    else:
        row = calibrate.serving(cell, 6, what, 1.0)
        assert row["sampled"] > 0 and row["checked_tokens"] >= cell["mix"]["check_tokens"]
        assert check.judge(row["program"], cell["limits"])[0] is True
        assert set(row["control"]) == set(row["program"])
    json.dumps(row)                               # a line of the --out file


def test_a_compile_inside_the_window_is_counted():
    counter = harness.CompileCounter()
    before = counter.count
    jax.jit(lambda a: a * 3 + 1)(np.arange(7.0))
    assert counter.count > before


def test_unknown_device_kind_and_missing_tpu_refuse():
    with pytest.raises(harness.RunFailed, match="no peaks on record"):
        harness.load_peaks("TPU v99")
    with pytest.raises(harness.RunFailed, match="no TPU"):
        harness.require_tpu(1)
