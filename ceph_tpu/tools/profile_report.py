"""Pipeline where-did-the-time-go report: render a captured profile
window as a human-readable phase-attribution table.

Accepts any of the profiler's JSON surfaces and normalizes them to one
view:

  * ``dump_pipeline_profile`` admin-socket output (full histograms),
  * ``telemetry.pipeline_profile_digest()`` (the MMgrReport carriage),
  * the mgr insights module's ``profile phases`` command output
    (cluster-merged), and
  * any wrapper document carrying one of these under a "profile" key.

It also accepts the tenant device-time ledger's surfaces — the
``dump_tenant_usage`` admin output, the MMgrReport ``tenant_usage``
digest, the mgr slo module's ``usage top`` merge, or a wrapper
document carrying a ``tenant_usage`` key — and renders a per-tenant
where-did-the-DEVICE-go table (device-seconds, cluster share, and
the per-engine/channel split) next to the phase table.

Output: per engine × kernel family, total attributed seconds and the
percentage each phase contributed (queue-wait, build, place, launch,
compute, materialize, deliver), the compile ledger (first-call jit
cost, separate from steady-state compute), device utilization, and
the mapping service's per-epoch device/delta/host-tail split.

Usage: python -m ceph_tpu.tools.profile_report [FILE|-]
"""

from __future__ import annotations

import json
import sys

from ceph_tpu.ops.telemetry import PHASES

#: mapping-service epoch phases, in pipeline order
MAPPING_PHASES = ("device", "delta", "host_tail")


def _from_hist_dump(d: dict) -> dict:
    """One engine's dump_pipeline_profile entry -> {kernel: {seconds,
    batches}}."""
    out = {}
    for kernel, per in (d.get("phases") or {}).items():
        secs = {ph: h.get("sum", 0.0) for ph, h in per.items()}
        batches = max((h.get("count", 0) for h in per.values()),
                      default=0)
        out[kernel] = {"seconds": secs, "batches": batches}
    return out


def normalize(doc: dict) -> dict:
    """Any accepted JSON shape -> {"engines", "compile",
    "utilization", "mapping"} (the insights ``profile phases``
    shape)."""
    if "profile" in doc and isinstance(doc["profile"], dict):
        doc = doc["profile"]          # wrapper document
    if "engines" in doc:              # insights profile phases output
        return {"engines": doc.get("engines", {}),
                "compile": doc.get("compile", {}),
                "utilization": doc.get("utilization", {}),
                "mapping": doc.get("mapping", {})}
    engines: dict = {}
    compile_: dict = {}
    util: dict = {}
    for engine in ("encode", "decode"):
        d = doc.get(engine)
        if not isinstance(d, dict):
            continue
        if "kernels" in d:            # digest form
            engines[engine] = {
                k: {"seconds": dict(row.get("seconds") or {}),
                    "batches": row.get("batches", 0)}
                for k, row in (d.get("kernels") or {}).items()}
        elif "phases" in d:           # full dump form
            engines[engine] = _from_hist_dump(d)
        if d.get("compile"):
            compile_[engine] = {
                k: {"seconds": c.get("seconds", 0.0),
                    "events": c.get("events", 0)}
                for k, c in d["compile"].items()}
        util[engine] = {"local": {
            "busy_seconds": d.get("busy_seconds", 0.0),
            "utilization": d.get("utilization", 0.0),
            "devices_seen": d.get("devices_seen", 1)}}
    return {"engines": engines, "compile": compile_,
            "utilization": util, "mapping": doc.get("mapping", {})}


def _pct(s: float, total: float) -> str:
    return f"{100.0 * s / total:5.1f}%" if total else "    --"


def normalize_tenant(doc: dict) -> dict | None:
    """Any tenant-usage surface -> {"tenants": {tenant:
    {"device_seconds", "channels": {(engine, channel): row}}},
    "total"} — or None when the document carries no tenant ledger.

    Accepts the admin dump / MMgrReport digest (``tenants`` mapping),
    the slo module's ``usage top`` output (``tenants`` LIST of ranked
    rows), and any wrapper carrying a ``tenant_usage`` key."""
    if isinstance(doc.get("tenant_usage"), dict):
        doc = doc["tenant_usage"]
    tenants = doc.get("tenants")
    if tenants is None:
        return None
    if isinstance(tenants, list):     # `usage top` ranked rows
        tenants = {r.get("tenant", "?"): r for r in tenants
                   if isinstance(r, dict)}
    if not isinstance(tenants, dict):
        return None
    out: dict = {}
    total = float(doc.get("total_device_seconds", 0.0) or 0.0)
    for tenant, trec in tenants.items():
        if not isinstance(trec, dict):
            continue
        channels = {}
        for eng, chans in (trec.get("engines") or {}).items():
            for ch, row in (chans or {}).items():
                channels[(eng, ch)] = row
        out[str(tenant)] = {
            "device_seconds": float(trec.get("device_seconds", 0.0)),
            "channels": channels}
    if not total:
        total = sum(t["device_seconds"] for t in out.values())
    return {"tenants": out, "total": total}


def render_tenant(doc: dict) -> str | None:
    """The per-tenant where-did-the-device-go table, or None when the
    document carries no tenant ledger."""
    n = normalize_tenant(doc)
    if n is None:
        return None
    lines: list[str] = []
    header = (f"{'tenant':<20} {'device_s':>10} {'share':>7} "
              f"{'engine':<8} {'channel':<14} {'chan_s':>10} "
              f"{'batches':>8} {'requests':>9}")
    lines.append("tenant device-time ledger (busy integral "
                 "apportioned by stripe share):")
    lines.append(header)
    lines.append("-" * len(header))
    total = n["total"]
    ranked = sorted(n["tenants"].items(),
                    key=lambda kv: -kv[1]["device_seconds"])
    for tenant, trec in ranked:
        first = True
        chans = sorted(trec["channels"].items()) or [((None, None), {})]
        for (eng, ch), row in chans:
            head = (f"{tenant:<20} {trec['device_seconds']:>10.4f} "
                    f"{_pct(trec['device_seconds'], total):>7}"
                    if first else f"{'':<20} {'':>10} {'':>7}")
            first = False
            if eng is None:
                lines.append(head)
                continue
            lines.append(
                f"{head} {eng:<8} {ch:<14} "
                f"{row.get('device_seconds', 0.0):>10.4f} "
                f"{row.get('batches', 0):>8} "
                f"{row.get('requests', 0):>9}")
    if not ranked:
        lines.append("(no tenant-attributed device time in this "
                     "window)")
    lines.append(f"{'total':<20} {total:>10.4f}")
    return "\n".join(lines)


def render(doc: dict) -> str:
    """The where-did-the-time-go table, as one printable string."""
    n = normalize(doc)
    lines: list[str] = []
    header = (f"{'engine':<8} {'kernel':<14} {'total_s':>9} "
              + " ".join(f"{ph:>11}" for ph in PHASES)
              + f" {'batches':>8}")
    lines.append(header)
    lines.append("-" * len(header))
    for engine in sorted(n["engines"]):
        for kernel in sorted(n["engines"][engine]):
            row = n["engines"][engine][kernel]
            secs = row.get("seconds") or {}
            total = sum(secs.values())
            cells = " ".join(
                f"{_pct(secs.get(ph, 0.0), total):>11}"
                for ph in PHASES)
            lines.append(f"{engine:<8} {kernel:<14} {total:>9.4f} "
                         f"{cells} {row.get('batches', 0):>8}")
    if not any(n["engines"].values()):
        lines.append("(no engine batches profiled in this window)")
    comp_rows = [(e, k, c) for e, per in sorted(n["compile"].items())
                 for k, c in sorted(per.items())]
    if comp_rows:
        lines.append("")
        lines.append("compile ledger (first-call jit cost, separate "
                     "from steady-state compute):")
        for engine, kernel, c in comp_rows:
            lines.append(f"  {engine:<8} {kernel:<14} "
                         f"{c.get('seconds', 0.0):>9.4f}s over "
                         f"{c.get('events', 0)} first-call batches")
    util_rows = [(e, who, u)
                 for e, per in sorted((n["utilization"] or {}).items())
                 for who, u in sorted(per.items())]
    if util_rows:
        lines.append("")
        lines.append("device utilization (busy-seconds integral over "
                     "the profiling window):")
        for engine, who, u in util_rows:
            lines.append(
                f"  {engine:<8} {who:<10} "
                f"util {100.0 * u.get('utilization', 0.0):5.1f}%  "
                f"busy {u.get('busy_seconds', 0.0):.4f}s  "
                f"devices {u.get('devices_seen', 1)}")
    mp = n.get("mapping") or {}
    secs = mp.get("seconds") or {}
    if secs:
        total = sum(secs.values())
        cells = "  ".join(
            f"{ph} {_pct(secs.get(ph, 0.0), total).strip()}"
            for ph in MAPPING_PHASES)
        lines.append("")
        lines.append(f"mapping epochs ({mp.get('epochs', 0)} computed,"
                     f" {total:.4f}s): {cells}")
    tenant = render_tenant(doc)
    if tenant is not None:
        lines.append("")
        lines.append(tenant)
    return "\n".join(lines)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] in ("-h", "--help"):
        print(__doc__)
        return 2
    if not argv or argv[0] == "-":
        text = sys.stdin.read()
    else:
        try:
            with open(argv[0]) as f:
                text = f.read()
        except OSError as e:
            print(f"profile_report: {e}", file=sys.stderr)
            return 1
    try:
        doc = json.loads(text)
    except ValueError as e:
        print(f"profile_report: input is not JSON: {e}",
              file=sys.stderr)
        return 1
    print(render(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
