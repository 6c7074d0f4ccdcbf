"""Serving shell (serve/batcher.py): mean `queue` span per request, ms."""

from bench import spans


def read(run):
    return spans.per_request_ms(run.traces, "queue")
