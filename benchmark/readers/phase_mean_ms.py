"""Mean milliseconds a request spent in the named phases of the serving
waterfall over the window: for each phase Δsum ÷ Δcount of
``pio_phase_seconds``, scraped from ``/metrics`` at the window's ends, and
the phases summed. (The histogram's fixed-bucket percentiles are too coarse
and are not read.)"""

METRIC = "pio_phase_seconds"


def read(run, phases: list):
    total, found = 0.0, False
    for phase in phases:
        label = f'{{phase="{phase}"}}'
        count = run.grown(f"{METRIC}_count{label}")
        if count > 0:
            total += 1e3 * run.grown(f"{METRIC}_sum{label}") / count
            found = True
    return total if found else None
