"""The table of peaks, keyed by ``device_kind``. An unknown device is an
error, never a default: a share of a peak nobody looked up means nothing."""
import json
import os


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind):
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise UnknownDevice(
            "no peaks for device_kind %r (table has %s)"
            % (device_kind, sorted(k for k in table if k[0] != "_")))
    return table[device_kind]
