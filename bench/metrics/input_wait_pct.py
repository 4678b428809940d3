"""Share of the window the trainer spent waiting on its input feed:
``DataPipeline.wait_s_total`` as ``Trainer.run`` reports it
(``TrainResult.input_stats['data_wait_s']``), over the window."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * ctx.input_wait_s / ctx.window_s
