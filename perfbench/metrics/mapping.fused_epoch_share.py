"""Percent of the epochs computed in the window that published fused
(device-resident) tables."""


def read(r):
    fused = r.delta("mapping.fused_epochs")
    total = fused + r.delta("mapping.unfused_epochs")
    return 100.0 * fused / total if total else None
