"""flatten_s.build: seconds per build outside the partition and the
prepare loop — the flatten of the (G, F) state into suffix order and the
``DeviceIndex`` assembly (``api._flatten_state``,
``DeviceIndex.from_prepare``).  Build wall time less ``t_vertical`` and
``t_prepare``."""


def read(run):
    if not run.builds:
        return None
    rest = [b["wall_s"] - b["t_vertical"] - b["t_prepare"] for b in run.builds]
    return sum(rest) / len(rest)
