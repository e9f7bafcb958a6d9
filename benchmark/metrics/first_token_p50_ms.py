"""Decode: time to first token, median over the window's requests: from
when a request was due to when the host had its first token, by the loop's
own ``decode_request`` record (``submit + token_us[0]``, the stamp of
``GenerateFuture.token_times[0]``; ``harness/requests.py``)."""
from benchmark.harness import requests, window


def read(ctx):
    values = [(rec["submit"] + rec["token_us"][0] / 1e6 - r["due"]) * 1e3
              for r, rec in requests.inside(ctx) if r.get("due") is not None]
    return window.percentile(values, 50) if values else None
