"""Mean host milliseconds the input feed's worker takes to make one batch
of the window: the program's ``feed.batch`` span (``data/pipeline.py``,
``source.batch_at`` and the transform) over the window's steps."""
from bench import spans


def read(ctx):
    rec = spans.recorder()
    if rec is None:
        return None
    return spans.mean_ms(spans.window_spans(rec, "feed.batch"))
