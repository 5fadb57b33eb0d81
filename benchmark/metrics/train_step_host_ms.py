"""Host time of one `Trainer.train_step` call (H2D of the batch and the
dispatch of the step; the device runs behind it): mean length of the
program's `train/step` spans in the window."""

from benchmark import ringread


def read(ctx):
    return ringread.mean_ms(ringread.window_spans(ctx), "train/step")
