"""flatten_host_s.build: seconds per build in the flatten of the (G, F)
state into suffix order and the ``DeviceIndex`` assembly (the
``build/flatten`` span around ``api._flatten_state`` and
``DeviceIndex.from_prepare``): ``flatten_s.build`` less the text's
packing and upload and the final sync.  Layer: core/api.py."""

from harness.spans import durations_s


def read(run):
    durs = durations_s(run, "build/flatten")
    if not run.builds or not durs:
        return None
    return sum(durs) / len(durs)
