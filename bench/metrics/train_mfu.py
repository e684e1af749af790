"""Whole training step's share of the chip's int8 peak, in %: the
operations the algorithm needs per image (``bench/work``) times the
images trained per second in the traced window, over chips × peak."""

from bench import peaks, work


def read(run):
    images, seconds = run.window.get("images"), run.window.get("seconds")
    if not images or not seconds:
        return None
    batch = run.mix["batch"]
    per_image = work.step_ops(run.config, batch) / batch
    peak = peaks.peaks(run.devices[0].device_kind)["int8_ops_per_s"]
    return 100.0 * per_image * images / seconds / (run.chips * peak)
