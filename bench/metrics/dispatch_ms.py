"""Mean host milliseconds of the jitted train step's call in each step
of the window: the program's ``train.dispatch`` span
(``training/loop.py:Trainer.run``), which holds the benchmark's
``bench.train_step`` span."""
from bench import spans


def read(ctx):
    rec = spans.recorder()
    if rec is None:
        return None
    return spans.mean_ms(rec.named(spans.DISPATCH))
