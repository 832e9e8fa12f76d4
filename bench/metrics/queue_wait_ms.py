"""Mean submit-to-batch wait of the window's requests, from the engine's
``serve_queue_wait_seconds`` histogram (its exact sum and count)."""


def read(rec):
    total, n = rec.queue_wait
    return total / n * 1e3 if n else None
