"""ProcessFleet: launch/observe/fault N beacon-node OS processes.

Every node goes through the real ``cli.py bn`` entry (``python -m
lighthouse_tpu ... bn ...``): interop genesis shared by an explicit
``--genesis-time``, deterministic wire identity (``--identity-seed``,
so a node keeps its peer id across SIGKILL + relaunch), an in-process
interop duty loop per node (``--interop-vc lo:hi`` — the simulator's
validator split, over real gossip), ephemeral or port-base port
assignment, and the startup handshake read back from the child's first
stdout JSON line (ports + peer id).

Orphan hygiene: a fleet registers itself with one module-level atexit
reaper; any child still alive on interpreter exit is SIGKILLed.  A
launch failure of node k tears down nodes 0..k-1 before raising, and
every child additionally carries ``--run-seconds`` as an in-child
backstop — three independent layers against orphaned beacon nodes.
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import subprocess
import sys
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field

from lighthouse_tpu.common import env as envreg
from lighthouse_tpu.common import flight_recorder as flight
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed

_LAUNCHES = REGISTRY.counter(
    "fleet_proc_launches_total", "beacon-node child processes launched")
_SIGKILLS = REGISTRY.counter(
    "fleet_proc_sigkills_total", "children killed with genuine SIGKILL")
_SIGTERMS = REGISTRY.counter(
    "fleet_proc_sigterms_total", "children stopped orderly via SIGTERM")
_REAPED = REGISTRY.counter(
    "fleet_proc_reaped_total",
    "children reaped by the teardown/atexit safety nets")


class FleetError(RuntimeError):
    pass


# -- the orphan backstop ------------------------------------------------------
#
# One process-wide reaper walks every live fleet at interpreter exit and
# SIGKILLs whatever is still running.  WeakSet: a collected fleet holds
# no children (its own shutdown() ran or its test failed hard — either
# way the procs it leaked are unreachable and the atexit sweep below is
# the last line, via the fleet that leaked them staying strongly
# referenced until shutdown()).

_LIVE_FLEETS: "weakref.WeakSet[ProcessFleet]" = weakref.WeakSet()
_ATEXIT_ARMED = False


def _reap_all() -> None:
    for fleet in list(_LIVE_FLEETS):
        fleet._reap(note="atexit")


def _arm_atexit() -> None:
    global _ATEXIT_ARMED
    if not _ATEXIT_ARMED:
        atexit.register(_reap_all)
        _ATEXIT_ARMED = True


@dataclass
class FleetNode:
    """One child process's book-keeping (the observer's node shape:
    ``.name`` + ``.state``)."""

    name: str
    index: int
    datadir: str
    state: str = "down"                 # "up" | "down"
    proc: subprocess.Popen | None = None
    http_port: int | None = None
    wire_port: int | None = None
    peer_id: str | None = None
    extra_env: dict = field(default_factory=dict)
    handshake: dict | None = None
    stdout_tail: deque = field(default_factory=lambda: deque(maxlen=64))

    @property
    def pid(self) -> int | None:
        return self.proc.pid if self.proc is not None else None

    @property
    def base_url(self) -> str:
        return f"http://127.0.0.1:{self.http_port}"

    @property
    def wire_addr(self) -> str:
        return f"127.0.0.1:{self.wire_port}"


class ProcessFleet:
    """N ``cli.py bn`` processes on localhost, one datadir each.

    ``port_base`` = 0 assigns ephemeral ports everywhere (the parent
    reads the truth back from each child's startup handshake); a
    nonzero base pins node i's wire port at ``base + 2i`` and HTTP port
    at ``base + 2i + 1`` (the wire/HTTP bind-retry seams degrade a
    collision to a neighbouring or ephemeral port, never a dead node).
    """

    def __init__(self, n_nodes: int, root: str, *,
                 network: str = "devnet", fork: str = "altair",
                 validators_per_node: int = 8,
                 slot_seconds: int | None = None,
                 genesis_time: int | None = None,
                 port_base: int | None = None,
                 max_run_seconds: float = 900.0,
                 env: dict | None = None,
                 extra_args: dict | None = None):
        if n_nodes < 1:
            raise FleetError("a fleet needs at least one node")
        self.n_nodes = n_nodes
        self.root = os.path.abspath(root)
        self.network = network
        self.fork = fork
        self.validators_per_node = validators_per_node
        self.n_validators = validators_per_node * n_nodes
        self.slot_seconds = (
            slot_seconds if slot_seconds is not None
            else envreg.get_int("LHTPU_FLEET_SLOT_S", 3) or 3)
        self.port_base = (
            port_base if port_base is not None
            else envreg.get_int("LHTPU_FLEET_PORT_BASE", 0) or 0)
        self.launch_deadline_s = float(
            envreg.get_float("LHTPU_FLEET_LAUNCH_S", 45.0) or 45.0)
        self.rejoin_deadline_s = float(
            envreg.get_float("LHTPU_FLEET_REJOIN_S", 90.0) or 90.0)
        self.max_run_seconds = max_run_seconds
        self.env = dict(env or {})
        self.extra_args = dict(extra_args or {})
        # genesis far enough out that every node is up before slot 0:
        # a shared EXPLICIT genesis_time is what makes N interop
        # geneses byte-identical across processes
        self.genesis_time = (
            genesis_time if genesis_time is not None
            else int(time.time()) + max(8, 2 * n_nodes))
        self.nodes: list[FleetNode] = [
            FleetNode(name=f"node-{i}", index=i,
                      datadir=os.path.join(self.root, f"node-{i}"))
            for i in range(n_nodes)]
        self._by_name = {n.name: n for n in self.nodes}
        # the currently-installed partition (name -> blocked peer ids):
        # a node restarted mid-window re-installs its edge set
        self._blocked_map: dict[str, set] = {}
        self._sources: list = []      # attached HttpSources to re-point
        self._lock = threading.Lock()
        os.makedirs(self.root, exist_ok=True)
        _LIVE_FLEETS.add(self)
        _arm_atexit()

    # -- observer adapter ---------------------------------------------------

    @property
    def live_nodes(self) -> list:
        return [n for n in self.nodes if n.state == "up"]

    def node(self, name: str) -> FleetNode:
        return self._by_name[name]

    def urls(self) -> dict:
        return {n.name: n.base_url for n in self.nodes
                if n.http_port is not None}

    def attach_source(self, source) -> None:
        """Keep an HttpSource's url map pointed at the live ports: an
        ephemeral-port node changes both ports on every relaunch."""
        source.urls.update(self.urls())
        source.per_node_rings = True   # each process owns its own ring
        self._sources.append(source)

    # -- launch -------------------------------------------------------------

    def launch(self) -> "ProcessFleet":
        """Start every node: node 0 first (the boot node), the rest
        dialing in through discovery.  Failure of node k tears down
        nodes 0..k-1 before raising — no survivors."""
        try:
            for node in self.nodes:
                boot = [n.wire_addr for n in self.nodes
                        if n.state == "up" and n is not node]
                self._launch_node(node, boot)
        except BaseException:
            self.shutdown()
            raise
        return self

    def _argv(self, node: FleetNode, boot: list) -> list:
        wire_port = (0 if not self.port_base
                     else self.port_base + 2 * node.index)
        http_port = (0 if not self.port_base
                     else self.port_base + 2 * node.index + 1)
        lo = node.index * self.validators_per_node
        hi = lo + self.validators_per_node
        argv = [
            sys.executable, "-m", "lighthouse_tpu",
            "--network", self.network,
            "--datadir", node.datadir,
            "bn",
            "--http-port", str(http_port),
            "--listen-port", str(wire_port),
            "--interop-validators", str(self.n_validators),
            "--genesis-fork", self.fork,
            "--genesis-time", str(self.genesis_time),
            "--bls-backend", "fake",
            "--disable-upnp",
            "--identity-seed", f"fleet-{node.name}",
            "--interop-vc", f"{lo}:{hi}",
            "--seconds-per-slot", str(self.slot_seconds),
            "--run-seconds", str(self.max_run_seconds),
        ]
        if boot:
            argv += ["--boot-nodes", ",".join(boot)]
        argv += list(self.extra_args.get(node.index, ()))
        return argv

    def _launch_node(self, node: FleetNode, boot: list) -> None:
        child_env = dict(os.environ)
        # drills never pay the AOT compile storm, and each child keeps
        # its flight dumps under its own datadir (the builder default)
        child_env.setdefault("LHTPU_AOT_STORE", "0")
        # one process per chip: these children run fake BLS at a few
        # dozen validators by construction, so on a machine with a chip
        # none of them may take it from its siblings (a real-BLS fleet
        # is one child per chip and does not go through this pin)
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env.update(self.env)
        child_env.update(node.extra_env)
        os.makedirs(node.datadir, exist_ok=True)
        stderr_path = os.path.join(node.datadir, "stderr.log")
        node.handshake = None
        node.stdout_tail.clear()
        handshake_ready = threading.Event()
        with open(stderr_path, "ab") as err:
            node.proc = subprocess.Popen(
                self._argv(node, boot), env=child_env,
                stdout=subprocess.PIPE, stderr=err, text=True)
        _LAUNCHES.inc()

        def _drain(proc=node.proc, n=node):
            # owns the pipe for the child's lifetime: the first JSON
            # line is the startup handshake (ports + peer id), the rest
            # is drained into a bounded tail so the pipe never fills
            for line in proc.stdout:
                n.stdout_tail.append(line.rstrip())
                if n.handshake is None and line.lstrip().startswith("{"):
                    try:
                        d = json.loads(line)
                    except ValueError:
                        continue
                    if d.get("running") == "bn":
                        n.handshake = d
                        handshake_ready.set()
            proc.stdout.close()

        threading.Thread(target=_drain, daemon=True,
                         name=f"fleet-drain-{node.name}").start()
        # wait for the handshake, but notice a dead child immediately —
        # a node that exits pre-handshake (bad flag, bind failure) must
        # fail the launch now, not after the full deadline
        deadline = time.monotonic() + self.launch_deadline_s
        while not handshake_ready.is_set():
            if node.proc.poll() is not None:
                time.sleep(0.2)      # let the drainer flush the tail
                break
            if time.monotonic() >= deadline:
                break
            handshake_ready.wait(0.25)
        if not handshake_ready.is_set() or node.proc.poll() is not None:
            rc = node.proc.poll()
            self._kill_proc(node)
            tail = "\n".join(list(node.stdout_tail)[-5:])
            raise FleetError(
                f"{node.name} failed to launch "
                f"(rc={rc}, deadline={self.launch_deadline_s}s): {tail}")
        hs = node.handshake
        node.http_port = hs.get("http_port")
        node.wire_port = hs.get("wire_port")
        node.peer_id = hs.get("peer_id")
        node.state = "up"
        for src in self._sources:
            src.urls[node.name] = node.base_url
        flight.emit("fleet_proc_launch", node=node.name, pid=node.pid,
                    wire_port=node.wire_port, http_port=node.http_port)
        # a node relaunched inside a partition window re-installs its
        # edge set before it can bridge the split
        blocked = self._blocked_map.get(node.name)
        if blocked:
            self._install_blocked(node, blocked)

    # -- lifecycle ----------------------------------------------------------

    def kill(self, name: str) -> FleetNode:
        """Genuine SIGKILL: no handler runs, the dirty marker stays
        dirty, and the next launch walks the PR 5 repair ladder."""
        node = self._by_name[name]
        if node.proc is None or node.proc.poll() is not None:
            raise FleetError(f"{name} is not running")
        os.kill(node.proc.pid, signal.SIGKILL)
        node.proc.wait(timeout=10)
        node.state = "down"
        _SIGKILLS.inc()
        flight.emit("fleet_proc_sigkill", node=name)
        return node

    def stop(self, name: str, deadline_s: float = 30.0) -> int:
        """Orderly SIGTERM: the cli handler runs Client.stop() —
        persist-frame, store close, clean dirty marker.  Returns the
        child's exit code."""
        node = self._by_name[name]
        if node.proc is None or node.proc.poll() is not None:
            raise FleetError(f"{name} is not running")
        node.proc.terminate()
        _SIGTERMS.inc()
        try:
            rc = node.proc.wait(timeout=deadline_s)
        except subprocess.TimeoutExpired:
            self._kill_proc(node)
            raise FleetError(
                f"{name} ignored SIGTERM for {deadline_s}s (killed)")
        node.state = "down"
        flight.emit("fleet_proc_sigterm", node=name, rc=rc)
        return rc

    def restart(self, name: str) -> FleetNode:
        """Relaunch a dead node over its surviving datadir: same
        identity seed (same peer id), same genesis — the child's own
        startup sweep + try_resume + range-sync do the actual rejoin."""
        node = self._by_name[name]
        if node.state == "up":
            raise FleetError(f"{name} is already running")
        boot = [n.wire_addr for n in self.live_nodes]
        self._launch_node(node, boot)
        return node

    def _kill_proc(self, node: FleetNode) -> None:
        if node.proc is not None and node.proc.poll() is None:
            try:
                os.kill(node.proc.pid, signal.SIGKILL)
                node.proc.wait(timeout=10)
                _REAPED.inc()
            except (OSError, subprocess.TimeoutExpired) as e:
                record_swallowed("fleet.kill_proc", e)
        node.state = "down"

    def _reap(self, note: str = "teardown") -> int:
        reaped = 0
        for node in self.nodes:
            if node.proc is not None and node.proc.poll() is None:
                self._kill_proc(node)
                reaped += 1
        if reaped:
            flight.emit("fleet_proc_reap", note=note, reaped=reaped)
        return reaped

    def shutdown(self, orderly: bool = False) -> None:
        """Tear the whole fleet down.  ``orderly`` SIGTERMs first (the
        clean-marker path); the SIGKILL sweep runs regardless, so no
        child survives a failed stop either."""
        if orderly:
            for node in self.nodes:
                if node.proc is not None and node.proc.poll() is None:
                    try:
                        self.stop(node.name)
                    except FleetError as e:
                        record_swallowed("fleet.shutdown_stop", e)
        self._reap()
        _LIVE_FLEETS.discard(self)

    def __enter__(self) -> "ProcessFleet":
        return self.launch()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- the admin seam (partition + runtime faults) ------------------------

    def _post(self, node: FleetNode, path: str, payload: dict,
              timeout_s: float = 5.0) -> dict:
        import urllib.request

        req = urllib.request.Request(
            node.base_url + path,
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return json.loads(resp.read())

    def _install_blocked(self, node: FleetNode, blocked: set) -> None:
        self._post(node, "/lighthouse/admin/partition",
                   {"blocked": sorted(blocked)})

    def partition(self, *groups) -> int:
        """Sever every cross-group pair at the socket level: each
        node's admin seam gets the peer ids it must refuse + drop
        (PartitionSet semantics — symmetric because both sides install
        the edge).  ``groups`` are sequences of node indices, the
        LocalNetwork.partition shape; nodes absent from all groups keep
        full connectivity.  Returns the number of severed pairs."""
        named = [[self.nodes[i] for i in g] for g in groups]
        blocked: dict[str, set] = {}
        severed = 0
        for gi, ga in enumerate(named):
            for gb in named[gi + 1:]:
                for a in ga:
                    for b in gb:
                        blocked.setdefault(a.name, set()).add(b.peer_id)
                        blocked.setdefault(b.name, set()).add(a.peer_id)
                        severed += 1
        self._blocked_map = blocked
        for name, peers in blocked.items():
            node = self._by_name[name]
            if node.state == "up":
                self._install_blocked(node, peers)
        flight.emit("fleet_proc_partition",
                    groups=[[n.name for n in g] for g in named],
                    severed=severed)
        return severed

    def heal(self) -> None:
        """Clear every installed edge set (live nodes now; a dead
        node's map entry is dropped so its relaunch comes up clean)."""
        self._blocked_map = {}
        for node in self.live_nodes:
            self._install_blocked(node, set())
        flight.emit("fleet_proc_heal")

    def admin_fault(self, name: str, env: dict, planes: list) -> dict:
        """Arm/disarm the env-knob fault planes inside a RUNNING node:
        the admin seam applies ``env`` to the child's environment and
        re-reads it through the same ``*_from_env`` paths the builder
        arms at startup."""
        node = self._by_name[name]
        return self._post(node, "/lighthouse/admin/fault",
                          {"env": env, "planes": planes})

    # -- scrape conveniences (HTTP only — the parent has no handles) --------

    def _get(self, node: FleetNode, path: str, timeout_s: float = 5.0):
        import urllib.request

        with urllib.request.urlopen(
                node.base_url + path, timeout=timeout_s) as resp:
            return json.loads(resp.read())

    def node_obs(self, name: str) -> dict:
        """One node's observatory roll-up (no cursor: the observer owns
        cursored scraping; this is the fleet's own spot-check)."""
        return self._get(
            self._by_name[name], "/lighthouse/observatory/node")["data"]

    def head_slot(self, name: str) -> int:
        return int(self.node_obs(name)["head"]["slot"])

    def finalized_epoch(self, name: str) -> int:
        return int(self.node_obs(name)["finalized"]["epoch"])

    def resume_mode(self, name: str) -> str | None:
        return (self.node_obs(name).get("lifecycle") or {}).get(
            "resume_mode")

    def max_head_slot(self) -> int:
        """Highest head slot over the LIVE fleet, scraped over HTTP."""
        heads = []
        for node in self.live_nodes:
            try:
                heads.append(self.head_slot(node.name))
            except Exception as e:
                record_swallowed("fleet.head_scrape", e)
        if not heads:
            raise FleetError("no live node answered a head scrape")
        return max(heads)

    def wait_until(self, cond, deadline_s: float, what: str,
                   poll_s: float = 0.5):
        """Poll ``cond`` (returning a truthy value or raising) until
        the deadline; the last error is folded into the failure."""
        t0 = time.monotonic()
        last_err: Exception | None = None
        while time.monotonic() - t0 < deadline_s:
            try:
                v = cond()
                if v:
                    return v
            except Exception as e:
                last_err = e
            time.sleep(poll_s)
        raise FleetError(
            f"timed out after {deadline_s}s waiting for {what}"
            + (f" (last error: {last_err})" if last_err else ""))


__all__ = ["FleetError", "FleetNode", "ProcessFleet"]
