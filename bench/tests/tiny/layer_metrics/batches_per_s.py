"""A per-layer metric that only the test's benchmark names."""


def read(rec):
    return len(rec.window_batches) / rec.window_s
