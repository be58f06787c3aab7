"""Median time of one step with every step synchronised (profiler off):
the step alone, without the overlap the measured window has."""
import statistics

LAYER = "compiled_trainer"
SOURCE = "host_clock"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return statistics.median(run.step_ms) if run.step_ms else None
