"""Planted fault ``slow_trace_stop``: the profiler's stop does not come back
in time.  ``jax.profiler.stop_trace`` sleeps ``SLEEP_S`` before it stops —
what four chips' trace did to PR 32's run (130 s and over 240 s for a 15 s
window).  A traced run has to end BY ITSELF, with the reason and the seconds
of each phase, exit 1 and no result line (``test_yardstick.py`` runs it with
the rule's budget cut well under the sleep)."""

import time

SLEEP_S = 45.0


def install():
    import jax

    plain = jax.profiler.stop_trace

    def stop_trace():
        time.sleep(SLEEP_S)
        plain()

    jax.profiler.stop_trace = stop_trace
