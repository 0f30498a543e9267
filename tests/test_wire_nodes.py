"""Two `bn` OS processes peer over localhost sockets (the round-2
verdict's "sockets or it didn't happen" done-condition): UDP discovery
via the boot node, TCP status handshake, block gossip, range sync.

Topology: node A (boot node) + a standalone `vc` proposing via A's HTTP
API; node B starts later from the same genesis with --boot-nodes=A and
must catch up to A's head through gossip + range sync.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _spawn(args):
    return subprocess.Popen(
        [sys.executable, "-m", "lighthouse_tpu", *args],
        env=_env(), cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)


def _first_json(proc, timeout=90):
    deadline = time.time() + timeout
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise AssertionError(
                    f"process exited rc={proc.returncode} before JSON")
            time.sleep(0.1)
            continue
        try:
            return json.loads(line)
        except ValueError:
            continue
    raise AssertionError("no JSON line from process")


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return json.loads(r.read())


def _poll(fn, cond, timeout, what):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            last = fn()
            if cond(last):
                return last
        except Exception:
            pass
        time.sleep(0.5)
    raise AssertionError(f"timeout waiting for {what}; last={last}")


def test_two_bn_processes_discover_gossip_and_sync():
    g_time = int(time.time()) + 2
    common = ["--network", "devnet"]
    bn_common = ["bn", "--http-port", "0", "--listen-port", "0",
                 "--bls-backend", "fake", "--interop-validators", "16",
                 "--genesis-fork", "altair",
                 "--genesis-time", str(g_time), "--run-seconds", "150"]
    a = _spawn([*common, *bn_common])
    procs = [a]
    try:
        a_info = _first_json(a)
        assert a_info["wire_port"], a_info

        vc = _spawn([
            "--network", "devnet", "vc",
            "--beacon-node", f"http://127.0.0.1:{a_info['http_port']}",
            "--interop-range", "0:16", "--run-seconds", "150"])
        procs.append(vc)

        # wait for A to have produced at least one block
        _poll(lambda: _get(a_info["http_port"], "/eth/v1/node/syncing"),
              lambda r: int(r["data"]["head_slot"]) >= 1,
              timeout=60, what="node A head to advance")

        b = _spawn([*common, *bn_common,
                    "--boot-nodes", f"127.0.0.1:{a_info['wire_port']}"])
        procs.append(b)
        b_info = _first_json(b)

        # B discovers A over UDP and TCP-connects
        _poll(lambda: _get(b_info["http_port"], "/eth/v1/node/peer_count"),
              lambda r: int(r["data"]["connected"]) >= 1,
              timeout=60, what="node B to connect to A")

        # B catches up to a moving head (gossip + range sync)
        def heads():
            ha = int(_get(a_info["http_port"],
                          "/eth/v1/node/syncing")["data"]["head_slot"])
            hb = int(_get(b_info["http_port"],
                          "/eth/v1/node/syncing")["data"]["head_slot"])
            return ha, hb

        _poll(heads, lambda h: h[1] >= 1 and h[0] - h[1] <= 1,
              timeout=90, what="node B to sync to A's head")

        # identity endpoint exposes the wire addresses
        ident = _get(b_info["http_port"], "/eth/v1/node/identity")["data"]
        assert ident["peer_id"] == b_info["peer_id"]
        assert ident["p2p_addresses"]
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
