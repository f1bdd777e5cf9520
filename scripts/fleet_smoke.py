#!/usr/bin/env python
"""Fleet observability smoke: router + controller + 2 demo replicas
over real HTTP.

Boots two ``serve --demo`` replica processes and one ``router`` process
(each exporting its tracer via --trace-out), drives generate requests
through the router, checks the live observability surfaces
(``/debug/dump`` flight bundle, the engine loop's phase seconds and
per-family dispatch counts on ``/metrics``). Then boots a ``controller``
over the SAME replicas with disaggregated roles (replica 0 = prefill,
replica 1 = decode) and sends one long-prompt request through the transfer path —
prefill computes the KV segment and pushes it replica-to-replica to the
decode target, whose generate full-hits. Shuts the fleet down, stitches
the per-process trace exports with ``trace-merge``, and validates the
merged document structurally: >= 4 process tracks, every replica
admission span's ``parent_span_id`` resolving to a dispatch span on a
different track, cross-process flow arrows present, and the disagg
chain controller dispatch -> export prefill -> transfer -> kv_ingest
joined under ONE trace id.

CI runs this as the fleet lane; it is also a one-command local repro:

    JAX_PLATFORMS=cpu python scripts/fleet_smoke.py
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

BOOT_TIMEOUT_S = 240  # demo replicas compile their programs first

# every HTTP call in this smoke derives its socket timeout from one
# deadline budget and propagates the remainder downstream via
# X-Deadline-Ms, so a wedged fleet fails the lane in bounded time
# instead of hanging on an unbounded urlopen
GET_BUDGET_S = 30.0
GENERATE_BUDGET_S = 120.0


def _deadline_headers(budget_s):
    return {"X-Deadline-Ms": str(int(budget_s * 1000))}


def get(addr, path, budget_s=GET_BUDGET_S):
    url = f"http://{addr['host']}:{addr['port']}{path}"
    req = urllib.request.Request(url, headers=_deadline_headers(budget_s))
    with urllib.request.urlopen(req, timeout=budget_s) as r:
        return r.read()


def post_generate(addr, body, budget_s=GENERATE_BUDGET_S):
    req = urllib.request.Request(
        f"http://{addr['host']}:{addr['port']}/v1/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 **_deadline_headers(budget_s)})
    with urllib.request.urlopen(req, timeout=budget_s) as r:
        return r.status, json.loads(r.read())


def wait_port_file(path, procs, timeout=BOOT_TIMEOUT_S):
    t0 = time.time()
    while time.time() - t0 < timeout:
        for p in procs:
            if p.poll() is not None:
                raise SystemExit(f"fleet process exited early: {p.args}")
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                return json.load(f)
        time.sleep(0.2)
    raise SystemExit(f"timed out waiting for {path}")


def prom_value(text, series):
    for line in text.splitlines():
        if line.startswith(series + " "):
            return float(line.split()[-1])
    raise SystemExit(f"{series} missing from /metrics")


def main():
    tmp = tempfile.mkdtemp(prefix="fleet-smoke-")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs = []
    traces = []
    try:
        port_files = []
        for i in range(2):
            pf = os.path.join(tmp, f"serve{i}.port")
            trace = os.path.join(tmp, f"serve{i}.trace.json")
            port_files.append(pf)
            traces.append(trace)
            procs.append(subprocess.Popen([
                sys.executable, "-m", "deeplearning4j_tpu", "serve",
                "--demo", "--port", "0", "--slots", "2",
                "--seq-len", "32", "--d-model", "32",
                "--n-layers", "2", "--n-heads", "4",
                "--port-file", pf, "--trace-out", trace,
                "--flight-dir", tmp,
                # replica 1 is the controller phase's decode target:
                # wire segments seat in its prefix cache
                *(["--prefix-cache"] if i == 1 else []),
            ], env=env))
        addrs = [wait_port_file(pf, procs) for pf in port_files]
        print(f"replicas up: {addrs}")

        rpf = os.path.join(tmp, "router.port")
        rtrace = os.path.join(tmp, "router.trace.json")
        traces.insert(0, rtrace)
        replica_flags = []
        for a in addrs:
            replica_flags += ["--replica", f"{a['host']}:{a['port']}"]
        procs.append(subprocess.Popen([
            sys.executable, "-m", "deeplearning4j_tpu", "router",
            *replica_flags, "--port", "0", "--port-file", rpf,
            "--trace-out", rtrace, "--flight-dir", tmp,
        ], env=env))
        raddr = wait_port_file(rpf, procs)
        print(f"router up: {raddr}")

        n_requests = 4
        for i in range(n_requests):
            status, body = post_generate(
                raddr, {"prompt": list(range(1, 8 + i)), "max_new": 3})
            assert status == 200 and body.get("tokens"), body
        print(f"{n_requests} requests routed OK")

        dump = json.loads(get(raddr, "/debug/dump"))
        assert any(e["kind"] == "dispatch" for e in dump["events"]), \
            "router flight recorder saw no dispatches"
        for a in addrs:
            rdump = json.loads(get(a, "/debug/dump"))
            assert rdump["reason"] == "debug_dump", rdump
        metrics = b"".join(get(a, "/metrics") for a in addrs).decode()
        assert "serve_program_dispatches_total{" in metrics, \
            "no per-family dispatch counts on /metrics"
        assert 'serve_loop_seconds_total{phase="dispatch"}' in metrics, \
            "no engine loop phase seconds on /metrics"
        print("debug dumps + engine loop metrics OK")

        # -- disaggregated phase: controller over the same replicas --
        cpf = os.path.join(tmp, "controller.port")
        ctrace = os.path.join(tmp, "controller.trace.json")
        traces.insert(0, ctrace)
        procs.append(subprocess.Popen([
            sys.executable, "-m", "deeplearning4j_tpu", "controller",
            "--replica", f"{addrs[0]['host']}:{addrs[0]['port']}=prefill",
            "--replica", f"{addrs[1]['host']}:{addrs[1]['port']}=decode",
            "--disagg-threshold", "12", "--port", "0",
            "--port-file", cpf, "--trace-out", ctrace,
            "--flight-dir", tmp,
        ], env=env))
        caddr = wait_port_file(cpf, procs)
        print(f"controller up: {caddr}")

        # 16 tokens >= threshold: prefill computes KV on replica 0,
        # pushes the segment to replica 1, the generate full-hits there
        status, body = post_generate(
            caddr, {"prompt": list(range(1, 17)), "max_new": 3})
        assert status == 200 and body.get("tokens"), body
        pmx = get(addrs[0], "/metrics").decode()
        assert prom_value(
            pmx, 'serve_transfers_total{result="ok"}') >= 1, \
            "prefill replica recorded no successful transfer"
        assert prom_value(pmx, "serve_transfer_bytes_total") > 0
        dmx = get(addrs[1], "/metrics").decode()
        assert prom_value(
            dmx, 'serve_kv_ingests_total{result="stored"}') >= 1, \
            "decode replica seated no wire segment"
        print("disagg transfer path OK (segment pushed + seated)")
    finally:
        # SIGINT = the CLI's clean path: drain, then export --trace-out
        for p in reversed(procs):
            if p.poll() is None:
                p.send_signal(signal.SIGINT)
        for p in procs:
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
    assert all(p.returncode == 0 for p in procs), \
        [(p.args[-1], p.returncode) for p in procs]

    merged_path = os.path.join(tmp, "merged.trace.json")
    subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu", "trace-merge",
         *traces, "-o", merged_path],
        check=True, env=env)
    with open(merged_path, encoding="utf-8") as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    pids = {e["pid"] for e in evs}
    assert len(pids) >= 4, f"expected >= 4 process tracks, got {pids}"
    dispatches = {
        e["args"]["span_id"]: e for e in evs
        if e.get("ph") == "X" and e["name"] == "dispatch"
        and "span_id" in e.get("args", {})
    }
    admissions = [
        e for e in evs
        if e.get("ph") == "X" and e["name"] == "prefill"
        and e.get("args", {}).get("parent_span_id")
    ]
    assert len(admissions) >= n_requests, \
        f"only {len(admissions)} admission spans joined the fleet trace"
    for adm in admissions:
        parent = dispatches.get(adm["args"]["parent_span_id"])
        assert parent is not None, f"unresolved parent: {adm}"
        assert parent["pid"] != adm["pid"], "parent link not cross-process"
        assert parent["args"]["trace_id"] == adm["args"]["trace_id"]
    n_flows = sum(1 for e in evs if e.get("ph") == "s")
    assert n_flows >= n_requests, f"only {n_flows} flow arrows"

    # the disagg chain: controller dispatch -> export prefill ->
    # transfer -> kv_ingest, one trace id end to end, each hop on a
    # different process track
    by_span = {e["args"]["span_id"]: e for e in evs
               if e.get("ph") == "X" and "span_id" in e.get("args", {})}
    transfers = [e for e in evs
                 if e.get("ph") == "X" and e["name"] == "transfer"]
    assert transfers, "no transfer span in the merged trace"
    tr = transfers[0]
    exp = by_span[tr["args"]["parent_span_id"]]
    assert exp["name"] == "prefill" and \
        exp["args"].get("prefix") == "export", exp
    ing = next(e for e in evs
               if e.get("ph") == "X" and e["name"] == "kv_ingest")
    assert ing["args"]["parent_span_id"] == tr["args"]["span_id"]
    tid = tr["args"]["trace_id"]
    assert exp["args"]["trace_id"] == ing["args"]["trace_id"] == tid
    root = by_span[exp["args"]["parent_span_id"]]
    assert root["name"] == "dispatch" and \
        root["args"].get("leg") == "prefill", root
    assert len({root["pid"], exp["pid"], ing["pid"]}) == 3, \
        "disagg chain does not cross three processes"
    print(f"merged trace OK: {len(pids)} tracks, "
          f"{len(admissions)} admission spans all parented to "
          f"dispatches, {n_flows} flow arrows, disagg chain "
          f"controller->prefill->transfer->ingest under trace {tid} "
          f"-> {merged_path}")


if __name__ == "__main__":
    main()
