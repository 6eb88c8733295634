"""serving_top — one-shot stats dump for a running inference server.

Connects to an InferenceServer endpoint, issues the `stats` RPC, and
prints a per-model table (QPS, latency percentiles, batch fill, queue
depth, sheds) plus one sub-row per replica execution lane (device id,
in-flight batches, lane queue depth, batches/rows executed) — the
operator's glance at whether the batch buckets and admission limits fit
the traffic and whether load is skewing across the device-placed
replicas.  The SLO column shows the burn-rate state machine's verdict
(ok / degr / BREACH — OBSERVABILITY.md "SLOs & burn rates") with one
sub-row per burning objective, and LIVE shows alive/total lane worker
threads ('!' marks a dead router or lane — the wedge indicator), both
from the `health` RPC verb.  REPL is the live replica count and FLEET
the fleet controller's per-model verdict (act / degr / PAGED, '-'
without a controller — SERVING.md "Fleet controller"), from the
`fleet` RPC verb; paged models keep their row (zero replicas, one
request from residency).  `--json` dumps the raw snapshot (plus
sibling "health" and "fleet" keys) for scripts.

Pointed at a federation frontend (SERVING.md "Federated serving") the
same `stats` verb answers with a merged cross-backend snapshot plus a
"federation" key, rendered as a backend table first: lease state
(live / DRAINING / LOST — draining is a live lease excluded from
placement, lost is an expired one), heartbeat age, queue depth,
frontend in-flight/placed counts, capacity, and the routing counters
(placed / spillover / shed / broken / repins).  A draining single
server shows a [DRAINING] banner from the health verb's `accepting`
flag.

Usage: python tools/serving_top.py HOST:PORT [--json]
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _fmt(v, unit=""):
    if v is None:
        return "-"
    if isinstance(v, float):
        return "%.1f%s" % (v, unit)
    return "%s%s" % (v, unit)


def _health_cols(name, health):
    """(SLO, LIVE) for one metrics lane key: the SLO state machine's
    verdict (ok/degr/BREACH, '-' when unmonitored) and thread liveness
    as alive/total worker threads across the model's lanes ('!' when a
    router or lane thread has died — the wedge indicator)."""
    if not health:
        return "-", "-"
    slo_col = "-"
    st = (health.get("slo") or {}).get(name)
    if st and st.get("monitored"):
        state = st.get("state") or "ok"
        slo_col = {"ok": "ok", "degraded": "degr",
                   "breach": "BREACH"}.get(state, state)
    plain = name.split("@", 1)[0]
    minfo = (health.get("models") or {}).get(plain)
    if not minfo:
        return slo_col, "-"
    alive = total = 0
    dead_router = False
    for lane in (minfo.get("lanes") or {}).values():
        live = lane.get("liveness") or {}
        if live.get("router_alive") is False:
            dead_router = True
        for l in live.get("lanes") or []:
            alive += int(l.get("alive", 0))
            total += int(l.get("workers", 0))
    live_col = "%d/%d" % (alive, total) if total else "-"
    if dead_router or (total and alive < total):
        live_col += "!"
    return slo_col, live_col


def _fleet_cols(name, desc, fleet):
    """(REPL, FLEET) for one metrics lane key: live replica count (0
    when paged) and the controller's per-model state — act / degr /
    PAGED, '-' when the server runs without a controller."""
    plain = name.split("@", 1)[0]
    d = desc.get(plain) or {}
    repl = 0 if d.get("paged") else d.get("replicas")
    fleet_col = "-"
    if fleet and fleet.get("enabled"):
        info = (fleet.get("models") or {}).get(plain)
        if info:
            fleet_col = {"active": "act", "degraded": "degr",
                         "paged": "PAGED"}.get(info.get("state"),
                                               info.get("state"))
        elif d.get("paged"):
            fleet_col = "PAGED"
        if fleet.get("dry_run") and fleet_col != "-":
            fleet_col += "?"
    elif d.get("paged"):
        fleet_col = "PAGED"
    return _fmt(repl), fleet_col


def _federation_lines(fed):
    """The front-door view (SERVING.md "Federated serving"): one row
    per leased backend — drain state, lease age vs TTL, heartbeat-fed
    queue depth, frontend in-flight/placed, capacity — plus recent
    losses and the routing counters (spillover-before-shed at a
    glance)."""
    backs = fed.get("backends") or {}
    counters = fed.get("counters") or {}
    inflight = fed.get("inflight") or {}
    placed = fed.get("placed") or {}
    lines = ["federation: %d backend(s), revision %s, ttl %ss  "
             "placed=%s spillover=%s shed=%s broken=%s repins=%s"
             % (len(backs), fed.get("revision"), fed.get("ttl_s"),
                sum(placed.values()), counters.get("spillover", 0),
                counters.get("shed", 0),
                counters.get("streams_broken", 0),
                counters.get("repins", 0)), ""]
    hdr = ("%-12s %-21s %-9s %6s %6s %6s %7s %11s  %s"
           % ("BACKEND", "ENDPOINT", "STATE", "AGE", "QUEUE",
              "INFLT", "PLACED", "MB", "MODELS"))
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for bid in sorted(backs):
        l = backs[bid]
        # DRAINING is visibly distinct from dead: the lease is still
        # here (alive, finishing streams), placement just skips it
        state = "DRAINING" if l.get("draining") else (
            "live" if l.get("accepting", True) else "no-accept")
        cap = l.get("capacity_mb") or 0
        mb = ("%.0f/%.0f" % (l.get("resident_mb", 0), cap)
              if cap else _fmt(round(l.get("resident_mb", 0))))
        lines.append(
            "%-12s %-21s %-9s %6s %6s %6s %7s %11s  %s"
            % (bid[:12], l.get("endpoint", "-")[:21], state,
               _fmt(l.get("age_s")),
               _fmt((l.get("load") or {}).get("queue_depth")),
               _fmt(inflight.get(bid, 0)), _fmt(placed.get(bid, 0)),
               mb, ",".join(sorted(l.get("models") or {})) or "-"))
    for bid, rec in sorted((fed.get("lost") or {}).items()):
        # dead, not draining: lease expired / hard transport evidence
        lines.append("%-12s %-21s %-9s %6s  (%s)"
                     % (bid[:12], rec.get("endpoint", "-")[:21],
                        "LOST", _fmt(rec.get("age_s")),
                        rec.get("reason", "?")))
    gf = fed.get("global_fleet")
    if gf:
        lines.append(
            "global fleet: ticks=%s dry_run=%s actions=%s"
            % (gf.get("ticks"), gf.get("dry_run"),
               gf.get("actions") or {}))
    lines.append("")
    return lines


def render(reply, health=None, fleet=None):
    stats = reply.get("stats", {})
    models = stats.get("models", {})
    desc = reply.get("models", {})
    banner = "server uptime %.0fs, %d model(s), %d tokens sent" \
        % (stats.get("uptime_sec", 0.0), len(models),
           stats.get("tokens_sent_total", 0))
    if health is not None and health.get("accepting") is False:
        # the drain-vs-dead disambiguation the health verb carries:
        # this server answers but refuses new admissions
        banner += "  [DRAINING]"
    lines = [banner, ""]
    if reply.get("federation"):
        # stats came from a federation frontend: backend table first
        lines.extend(_federation_lines(reply["federation"]))
    hdr = ("%-14s %5s %6s %8s %8s %7s %7s %7s %7s %6s %6s %6s %7s "
           "%7s %7s %5s %5s %5s %7s %6s %5s %5s %6s"
           % ("MODEL", "PREC", "VER", "QPS", "REQS", "p50ms", "p95ms",
              "p99ms", "FILL", "BKT%", "QUEUE", "SHED", "CCH/M",
              "TTFT95", "TPS", "TPD", "OCC%", "ACC%", "SLO", "LIVE",
              "REPL", "MESH", "FLEET"))
    lines.append(hdr)
    lines.append("-" * len(hdr))
    described = set()
    for name in sorted(models):
        # lanes key as 'name@precision' for non-fp32 (QUANTIZE.md):
        # render the plain model name + a PREC column, and resolve the
        # describe() info (and the lane's routed version) by plain name
        m = models[name]
        lat = m.get("latency_ms", {})
        plain = m.get("model", name)
        prec = m.get("precision", "fp32")
        d = desc.get(plain, {})
        ver = (d.get("precisions") or {}).get(prec, d.get("latest"))
        cc = m.get("compile_cache", {})
        # compile-cache hits/misses across this model's loads + flips:
        # "N/0" on a warm boot means zero fresh compilations
        cc_col = "%s/%s" % (cc.get("hits", 0), cc.get("misses", 0)) \
            if cc else "-"
        # decode models (SERVING.md continuous batching): TTFT p95,
        # aggregate tokens/sec, and slot occupancy; "-" otherwise.
        # ACC% is the speculative-decoding lifetime draft accept rate
        # (absent without a draft — target-only lanes show "-").
        # TPD is lifetime tokens-per-dispatch — the fused-decode
        # amortization ratio (≈ the window's cap when the lane is full)
        ttft = (m.get("ttft_ms") or {}).get("p95")
        tps = m.get("tokens_per_sec")
        dispatches = m.get("decode_dispatches")
        tpd = (round(m.get("decode_tokens", 0) / float(dispatches), 1)
               if dispatches else None)
        occ = m.get("slot_occupancy")
        acc = m.get("spec_accept_rate")
        slo_col, live_col = _health_cols(name, health)
        repl_col, fleet_col = _fleet_cols(name, desc, fleet)
        # MESH: member-device count of this model's replica lanes
        # (SERVING.md "Mesh replicas") — '-' for plain one-chip lanes,
        # NxM-style counts come from the lane rows (live) or describe()
        sizes = [int(r.get("mesh", 1) or 1)
                 for r in m.get("replicas") or []]
        mesh_max = max(sizes or [int(d.get("mesh_size", 1) or 1)])
        # 'NTP' marks tensor-parallel lanes (SERVING.md
        # "Tensor-parallel compute"): the mesh runs the partitioned
        # program instead of gather-and-replicate
        tp_on = any(r.get("tp") for r in m.get("replicas") or []) \
            or bool(d.get("mesh_tp"))
        mesh_col = ("%d%s" % (mesh_max, "TP" if tp_on else "")
                    if mesh_max > 1 else "-")
        lines.append(
            "%-14s %5s %6s %8s %8s %7s %7s %7s %7s %6s %6s %6s %7s "
            "%7s %7s %5s %5s %5s %7s %6s %5s %5s %6s"
            % (plain[:14], prec[:5], _fmt(ver),
               _fmt(m.get("qps_recent")), _fmt(m.get("requests")),
               _fmt(lat.get("p50")), _fmt(lat.get("p95")),
               _fmt(lat.get("p99")), _fmt(m.get("batch_fill")),
               _fmt(round(100.0 * m.get("bucket_fill_ratio", 0.0), 1)),
               _fmt(m.get("queue_depth")), _fmt(m.get("shed")),
               cc_col, _fmt(ttft), _fmt(tps), _fmt(tpd),
               _fmt(round(100.0 * occ, 1) if isinstance(occ, float)
                    and occ >= 0 else None),
               _fmt(round(100.0 * acc, 1)
                    if isinstance(acc, float) else None),
               slo_col, live_col, repl_col, mesh_col, fleet_col))
        st = (health or {}).get("slo", {}).get(name)
        if st and st.get("monitored") and st.get("burn"):
            # one sub-row per burning objective: which SLI is eating
            # the error budget and how fast (burn 1.0 = sustainable)
            for objective, b in sorted(st["burn"].items()):
                if any(v for v in b.values() if v):
                    lines.append(
                        "    slo %-12s fast=%-8s slow=%-8s"
                        % (objective, _fmt(b.get("fast"), "x"),
                           _fmt(b.get("slow"), "x")))
        fm = ((fleet or {}).get("models") or {}).get(plain)
        if fm and fm.get("fault_in_ms") is not None \
                and plain not in described:
            # last fault-in: what the page/fault cycle cost (reload +
            # warm across the lane set, warm compile cache)
            lines.append("    fleet fault_in=%sms (%s) idle=%ss"
                         % (_fmt(fm["fault_in_ms"]),
                            fm.get("fault_in_trigger", "?"),
                            _fmt(fm.get("idle_s"))))
        if d.get("buckets") and plain not in described:
            described.add(plain)
            extra = ""
            if d.get("decode"):
                extra = " decode_slots=%s max_seq_len=%s" % (
                    d.get("decode_slots"), d.get("max_seq_len"))
                if d.get("fuse_steps"):
                    # the cap of the lane's per-dispatch window
                    extra += " fuse_steps=%s" % (d["fuse_steps"],)
                if d.get("spec_k"):
                    extra += " spec_k=%s draft=%s" % (
                        d["spec_k"], d.get("draft"))
            if d.get("precisions"):
                extra += " precisions=%s" % (d["precisions"],)
            if d.get("ab_weights"):
                extra += " ab=%s" % (d["ab_weights"],)
            lines.append("    buckets=%s versions=%s replicas=%s%s"
                         % (d["buckets"], d.get("versions"),
                            d.get("replicas", 1), extra))
        shed_pri = m.get("shed_by_priority")
        if shed_pri:
            lines.append("    shed_by_priority=%s" % (shed_pri,))
        for r in m.get("replicas") or []:
            # one sub-row per replica lane: load skew across devices
            # must be visible at a glance.  A mesh lane (SERVING.md
            # "Mesh replicas") renders its member-device count here and
            # one indented sub-row per member chip; a lane killed by
            # member loss stays visible with a DEAD marker.
            dev = str(r.get("device") or "-")
            mesh = int(r.get("mesh", 1) or 1)
            if mesh == 1:
                label = dev
            elif r.get("tp"):
                label = "mesh(%d,tp)" % mesh
            else:
                label = "mesh(%d)" % mesh
            row = ("    r%-3s %-11s %9s %9s %10s %12s"
                   % (r.get("replica"), label[:11],
                      "inflt=%s" % _fmt(r.get("inflight")),
                      "queue=%s" % _fmt(r.get("queue")),
                      "batches=%s" % _fmt(r.get("batches")),
                      "rows=%s" % _fmt(r.get("rows"))))
            if r.get("dispatch_ms") is not None:
                row += "  disp=%sms" % _fmt(r.get("dispatch_ms"))
            if r.get("dead"):
                row += "  DEAD(%s)" % str(r["dead"])[:40]
            lines.append(row)
            if mesh > 1:
                # per-member sub-rows: an SPMD dispatch lands on every
                # member at once, so each shows the lane's dispatch
                # EWMA — the per-chip time the TP bandwidth model
                # predicts at ~1/mesh of gather mode
                disp = ("  disp=%sms" % _fmt(r["dispatch_ms"])
                        if r.get("dispatch_ms") is not None else "")
                for member in dev.split("+"):
                    lines.append("         + %s%s" % (member, disp))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("endpoint", help="HOST:PORT of the inference server")
    ap.add_argument("--json", action="store_true",
                    help="raw snapshot JSON instead of the table")
    args = ap.parse_args(argv)

    from paddle_tpu.serving import ServingClient
    cli = ServingClient(args.endpoint)
    try:
        reply = cli.stats()
        try:
            health = cli.health()
        except Exception:
            health = None  # pre-health server: columns degrade to '-'
        try:
            fleet = cli.fleet()
        except Exception:
            fleet = None  # pre-fleet server: columns degrade to '-'
    finally:
        cli.close()
    if args.json:
        # both ride as SIBLING keys: the pinned stats schema the
        # dashboards scrape is untouched
        if health is not None:
            reply = dict(reply, health=health)
        if fleet is not None:
            reply = dict(reply, fleet=fleet)
        print(json.dumps(reply, indent=1, default=str))
    else:
        print(render(reply, health=health, fleet=fleet))
    return 0


if __name__ == "__main__":
    sys.exit(main())
