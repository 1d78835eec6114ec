"""One module per kind of deployment; `harness/cell.py` says what each
gives.  What they share is here."""

#: fault counters of a dispatch engine that mean the host stood in for
#: the device (the engines' ladder hides a refused kernel otherwise)
_FAULT_KEYS = ("fallback_batches", "breaker_opens", "thread_deaths")


def engine_faults(faults: dict) -> int:
    """Of a dispatch engine's ``fault_dump()``: how often the host stood
    in for the device."""
    return sum(faults[key] for key in _FAULT_KEYS)
