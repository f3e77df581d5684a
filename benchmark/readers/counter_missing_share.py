"""What part of ``whole``'s growth over the window ``part``'s growth does NOT
cover, in %: the padding among the tokens the device computed."""


def read(run, part: list, whole: list):
    if not all(k in run.counters_end for k in part + whole):
        return None
    below = sum(run.grown(k) for k in whole)
    return 100.0 * (1.0 - sum(run.grown(k) for k in part) / below) if below > 0 else None
