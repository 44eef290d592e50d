"""The harness's training loop against ``train.main``: same flags, seed and
shards, the same loss at every one of 3 steps."""

from bench_tiny import tiny_cell
from benchmark import harness, train_cell


def test_same_loss_as_train_main_at_every_step(monkeypatch):
    import jax

    from gpt_2_distributed_tpu import train
    from gpt_2_distributed_tpu.metrics.tracker import StatsTracker

    cell = tiny_cell("train")
    seed = 9
    trainer = train_cell.Trainer(cell, seed, harness.Spans())
    try:
        ours = []
        for _ in range(3):
            metrics = trainer.dispatch()
            ours.append(float(jax.block_until_ready(metrics).loss))
    finally:
        trainer.close()

    theirs = {}
    update = StatsTracker.update

    def recording(self, step, count_tokens=True, **metrics):
        if "loss" in metrics:
            theirs[step] = float(metrics["loss"])
        return update(self, step, count_tokens, **metrics)

    monkeypatch.setattr(StatsTracker, "update", recording)
    train.main(cell["program"].trainer_flags(
        cell["config_file"], cell["mix"], trainer.data_dir, seed)
               + ["--max_steps", "3", "--cli_every", "1"])
    assert [theirs[i] for i in (1, 2, 3)] == ours
