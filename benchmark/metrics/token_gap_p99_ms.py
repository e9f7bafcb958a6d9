"""Decode: gap between consecutive tokens of one request as its client
sees them, 99th percentile over every such gap of the window's requests,
by the loop's own ``decode_request`` records (differences of ``token_us``;
``harness/requests.py``): a step behind a co-rider's prefill pass, a stall
of the loop, a step that waited for the host."""
from benchmark.harness import requests, window


def read(ctx):
    gaps = requests.token_gaps_ms(ctx)
    return window.percentile(gaps, 99) if gaps else None
