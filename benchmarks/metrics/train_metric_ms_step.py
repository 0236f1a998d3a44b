"""The train metrics on the host per training step (``eval_train =
1``): ``np.asarray(labels)`` and the ``train_metric.add_eval`` loop over
the chunk's outputs; the program's ``metric`` stage (span
``train.metric``)."""

from benchmarks.lib import stages

LAYER = "step programs"
UNIT = "ms/step"
SOURCE = "program_span"
MOVES = "train_samples_s_chip"


def read(run):
    return stages.ms_per_step(run, "metric")
