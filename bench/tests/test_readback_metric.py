"""The front end's readback reader, readback_us_per_flush."""
import numpy as np

from test_metrics import make_run, reader


def test_readback_reader(tiny_bench):
    """Readback per flush: None on stats without the counter (an older
    server) and on no flush; else 1e6 x readback time / flushes."""
    read = reader(tiny_bench, "readback_us_per_flush")
    run = make_run(np.zeros(10), np.zeros(10), np.ones(10))
    run.stats.update(batches=400)
    assert read(run) is None
    run.stats.update(batches=0, readback_time_s=0.0)
    assert read(run) is None
    run.stats.update(batches=400, readback_time_s=0.05)
    assert np.isclose(read(run), 125.0)
