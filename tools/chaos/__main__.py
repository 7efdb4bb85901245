"""Standalone chaos proxy: `python -m tools.chaos --listen 7001
--connect learner-host:7000 --garble 0.01 --delay 0.005`.

Point actor hosts at the proxy's listen port instead of the learner
and watch the run's obs artifacts attribute every injected fault
(wire_decode_errors, peer_disconnects, reconnect latencies). SIGINT
prints the fault stats and exits.

Reproducible drills: the startup line prints the RNG seed, and
`--scenario <name>` runs a named preset built from the set_fault/cut
primitives — each phase transition is printed, so any drill can be
re-run exactly from a log (same seed, same
scenario, same phase schedule). A scenario takes over fault control:
its clean phases reset ALL rates, including ones given on the
command line.

Forensics: `--cycles N --forensics-dir DIR` bounds a scenario to N
full phase cycles and turns the drill into a postmortem assertion —
the proxy keeps its own flight recorder of every injected fault
(obs/blackbox.py), dumps it into DIR next to whatever blackbox dumps
the fleet under test wrote there, bundles the lot
(obs/postmortem.py), and exits nonzero unless the bundle's root-cause
walk attributes the drill to an injected component by name.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from tools.chaos.proxy import ChaosProxy

# name -> cyclic phase list of (duration_s, action); action is "cut"
# (sever all live sockets once), "clean" (all fault rates to 0), or a
# set_fault(**kwargs) dict. Durations are fixed so a logged drill
# replays exactly.
SCENARIOS = {
    # periodic learner blip: sever everything, give the fleet a clean
    # recovery window, repeat — the supervised-reconnect drill
    "kill-recover": [(0.0, "cut"), (20.0, "clean")],
    # bursts of payload corruption against a clean baseline — the
    # wire-decode-error accounting drill
    "garble-storm": [(5.0, {"garble_rate": 0.05}), (10.0, "clean")],
    # fast alternation of heavy drop and clean — the flapping-sensor
    # drill the remediation plane's hysteresis must not oscillate on
    "flap": [(2.0, {"drop_rate": 0.5}), (2.0, "clean")],
}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--listen", type=int, required=True,
                    help="local port to accept actor-host connections on")
    ap.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="upstream learner ingest address")
    ap.add_argument("--drop", type=float, default=0.0)
    ap.add_argument("--delay", type=float, default=0.0)
    ap.add_argument("--truncate", type=float, default=0.0)
    ap.add_argument("--garble", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cut-every", type=float, default=0.0,
                    help="seconds between cutting all live connections "
                         "(0 = never): the periodic learner-blip drill")
    ap.add_argument("--scenario", choices=sorted(SCENARIOS),
                    default=None,
                    help="named fault-schedule preset; phase "
                         "transitions are printed so the drill can be "
                         "re-run exactly from any log")
    ap.add_argument("--cycles", type=int, default=0,
                    help="with --scenario: stop after N full phase "
                         "cycles instead of running forever (0 = "
                         "forever)")
    ap.add_argument("--forensics-dir", default=None, metavar="DIR",
                    help="record every injected fault into a flight "
                         "recorder, dump it to DIR on drill end, "
                         "bundle DIR's blackbox dumps into "
                         "POSTMORTEM.json, and exit nonzero unless "
                         "the root cause names an injected component")
    args = ap.parse_args(argv)
    host, port = args.connect.rsplit(":", 1)
    proxy = ChaosProxy(host, int(port), listen_port=args.listen,
                       drop_rate=args.drop, delay_s=args.delay,
                       truncate_rate=args.truncate,
                       garble_rate=args.garble, seed=args.seed)
    scen = f" scenario={args.scenario}" if args.scenario else ""
    print(f"chaos proxy: :{proxy.port} -> {host}:{port} "
          f"seed={args.seed}{scen}", flush=True)
    recorder = None
    if args.forensics_dir:
        recorder = _make_recorder(args.forensics_dir)
    try:
        if args.scenario:
            _run_scenario(proxy, args.scenario, recorder=recorder,
                          cycles=args.cycles)
        else:
            _run_static(proxy, args.cut_every)
    except KeyboardInterrupt:
        pass
    finally:
        proxy.stop()
        print(f"chaos proxy stats: {proxy.stats}", file=sys.stderr)
    if recorder is not None:
        return _bundle_and_attribute(args.forensics_dir, recorder)
    return 0


def _make_recorder(forensics_dir: str):
    """Flight recorder for the proxy's OWN injected-fault log — the
    drill's ground truth, dumped next to the victims' boxes."""
    from ape_x_dqn_tpu.obs.blackbox import FlightRecorder

    class _Sink:  # minimal obs facade (the proxy has no Obs plane)
        def __init__(self):
            self.ctr: dict[str, int] = {}

        def count(self, name, n=1):
            self.ctr[name] = self.ctr.get(name, 0) + n

    os.makedirs(forensics_dir, exist_ok=True)
    return FlightRecorder(_Sink(), peer="chaos-proxy",
                          out_dir=forensics_dir)


# components the proxy's fault primitives act on; the postmortem root
# cause must name one of these (or a victim's own dump must)
_INJECTED = ("link",)


def _bundle_and_attribute(forensics_dir: str, recorder) -> int:
    """Dump the proxy's own box, bundle every blackbox-*.json in the
    forensics dir, walk the merged timeline backwards, and demand the
    root cause name an injected component."""
    from ape_x_dqn_tpu.obs import postmortem, report

    recorder.dump("drill_complete", component="chaos-proxy")
    bpath = os.path.join(forensics_dir, "POSTMORTEM.json")
    bundle = postmortem.build_bundle(forensics_dir, out_path=bpath)
    root = report.postmortem_root_cause(bundle) or {}
    events = [e for e in (root.get("anomaly"), root.get("terminal"))
              if e]
    victims = [c for d in bundle["dumps"]
               if d.get("peer") != "chaos-proxy"
               for c in (d.get("component"),) if c]
    named = set(_INJECTED) | set(victims)
    attributed = any(e.get("component") in named for e in events)
    rc_line = report.format_postmortem(bundle).splitlines()[-1]
    print(f"chaos forensics: bundle {bpath} ({len(bundle['dumps'])} "
          f"dumps, {len(bundle['skipped_dumps'])} skipped) — "
          f"{rc_line}", flush=True)
    if not bundle["dumps"] or not attributed:
        print(f"chaos forensics FAIL: root cause does not attribute "
              f"an injected/victim component ({sorted(named)})",
              file=sys.stderr)
        return 1
    return 0


def _run_static(proxy: ChaosProxy, cut_every: float) -> None:
    last_cut = time.monotonic()
    while True:
        time.sleep(0.5)
        if cut_every > 0 \
                and time.monotonic() - last_cut >= cut_every:
            n = proxy.cut()
            last_cut = time.monotonic()
            print(f"chaos proxy: cut {n} sockets", flush=True)


def _run_scenario(proxy: ChaosProxy, name: str, recorder=None,
                  cycles: int = 0) -> None:
    phases = SCENARIOS[name]
    i = 0
    while True:
        if cycles > 0 and i >= cycles * len(phases):
            return
        duration, action = phases[i % len(phases)]
        if action == "cut":
            n = proxy.cut()
            print(f"chaos scenario {name}: cut {n} sockets",
                  flush=True)
            if recorder is not None:
                recorder.record("kill", component="link",
                                scenario=name, sockets=n)
        elif action == "clean":
            proxy.clean()
            print(f"chaos scenario {name}: clean", flush=True)
            if recorder is not None:
                recorder.record("remediation", component="link",
                                scenario=name, action="clean")
        else:
            proxy.set_fault(**action)
            print(f"chaos scenario {name}: set_fault {action}",
                  flush=True)
            if recorder is not None:
                recorder.record("wedge", component="link",
                                scenario=name, **action)
        # a bounded drill skips the final phase's dwell: the schedule
        # is over, only the bundle assertion remains
        last = cycles > 0 and i + 1 >= cycles * len(phases)
        if duration > 0 and not last:
            time.sleep(duration)
        i += 1


if __name__ == "__main__":
    raise SystemExit(main())
