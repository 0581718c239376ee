"""Training steps: ``Model.fit``'s trainer on the reader's synthetic Zipf
source, at the configuration's batch and mesh. The window runs whole calls
of ``Trainer.train`` (``STEPS_PER_CALL`` steps each, each step synced on its
loss as the trainer does) until ``seconds`` have passed;
``train_samples_per_s`` is every sample of every step in the window over
the window's length.
"""
import math

import training

#: steps per ``Trainer.train`` call in the window; the window's clock is
#: read between calls
STEPS_PER_CALL = 4


def run(r):
    tr = training.Training(r)
    r.setup_done()
    steps, bad = 0, 0
    with r.window() as w:
        while True:
            losses = tr.steps(STEPS_PER_CALL)
            steps += STEPS_PER_CALL
            bad += sum(1 for x in losses if not math.isfinite(x))
            if w.elapsed() >= r.seconds:
                break
    r.attempted, r.failed = steps, bad
    r.e2e["train_samples_per_s"] = steps * tr.batch / w.seconds
    r.layer.update({"steps": steps, "batch": tr.batch,
                    "window_s": w.seconds})
    r.read_memory()
    tr.free()
    r.checks.update(tr.check())
