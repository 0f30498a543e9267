"""`lighthouse-tpu` CLI: one binary multiplexing every role.

Rebuild of /root/reference/lighthouse/src/main.rs:87,412-414,669-736
(bn / vc / account_manager / validator_manager / database_manager
subcommands) at the flag surface this client consumes.  Run with
``python -m lighthouse_tpu <subcommand>``.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time


def _positive_int(value: str) -> int:
    n = int(value)
    if n < 1:
        raise argparse.ArgumentTypeError("must be >= 1")
    return n


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="lighthouse-tpu",
        description="TPU-native Ethereum consensus client")
    p.add_argument("--network", default="devnet",
                   help="built-in network name (mainnet/minimal/devnet)")
    p.add_argument("--network-config", default=None,
                   help="path to a config.yaml overriding --network")
    p.add_argument("--datadir", default=None,
                   help="persistent DB directory (default: in-memory)")
    sub = p.add_subparsers(dest="command", required=True)

    bn = sub.add_parser("bn", help="run a beacon node")
    bn.add_argument("--http-port", type=int, default=5052)
    bn.add_argument("--execution-endpoint", default=None)
    bn.add_argument("--execution-jwt", default=None,
                    help="hex JWT secret for the engine API")
    bn.add_argument("--slasher", action="store_true")
    bn.add_argument("--wire-transport", default="tcp",
                    choices=("tcp", "quic"),
                    help="stream transport for gossip/RPC "
                         "(quic = the UDP stream transport)")
    bn.add_argument("--disable-upnp", action="store_true",
                    help="skip UPnP gateway port mapping (reference flag)")
    bn.add_argument("--slasher-backend", default="native",
                    choices=("memory", "native", "sqlite"),
                    help="slasher DB engine (reference --slasher-backend)")
    bn.add_argument("--interop-validators", type=int, default=64,
                    help="interop genesis validator count (dev networks)")
    bn.add_argument("--genesis-fork", default="capella")
    bn.add_argument("--genesis-time", type=int, default=None,
                    help="interop genesis time (default: now); nodes "
                         "sharing a devnet must pass the same value")
    bn.add_argument("--run-seconds", type=float, default=None,
                    help="exit after N seconds (default: run forever)")
    bn.add_argument("--bls-backend", default="auto",
                    choices=["auto", "tpu", "reference", "fake"],
                    help="BLS data plane: auto = device pipeline when a "
                         "TPU is attached, pure-Python reference otherwise")
    bn.add_argument("--listen-port", type=int, default=None,
                    help="TCP+UDP wire port (0 = ephemeral); omit to run "
                         "without the socket network stack")
    bn.add_argument("--seconds-per-slot", type=int, default=None,
                    help="dev-only slot pacing override (process-fleet "
                         "devnets walk fast slots; None = the spec's)")
    bn.add_argument("--identity-seed", default=None,
                    help="deterministic wire identity seed: the node "
                         "keeps its peer id across restarts (fleets); "
                         "None = random per start")
    bn.add_argument("--interop-vc", default=None, metavar="LO:HI",
                    help="run an in-process duty loop for interop "
                         "validators [LO, HI) — the process-fleet "
                         "analogue of the simulator's validator split")
    bn.add_argument("--boot-nodes", default=None,
                    help="comma-separated host:port discovery bootstrap "
                         "addresses")
    bn.add_argument("--builder", default=None,
                    help="external block-builder (MEV) endpoint URL")
    bn.add_argument("--trusted-setup", default=None,
                    help="path to the KZG ceremony trusted_setup.json "
                         "(consensus-specs format)")
    bn.add_argument("--monitoring-endpoint", default=None,
                    help="remote monitoring service URL to POST "
                         "node/system metrics to every 60s")

    vc = sub.add_parser("vc", help="run a validator client")
    vc.add_argument("--beacon-node", default="http://127.0.0.1:5052")
    vc.add_argument("--validators-dir", default=None,
                    help="directory of EIP-2335 keystores")
    vc.add_argument("--keystore-password", default="")
    vc.add_argument("--builder-blocks", action="store_true",
                    help="propose via the blinded (builder) round trip")
    vc.add_argument("--interop-range", default=None,
                    help="START:END interop validator indices (dev)")
    vc.add_argument("--run-seconds", type=float, default=None)
    vc.add_argument("--monitoring-endpoint", default=None,
                    help="remote monitoring service URL to POST "
                         "validator/system metrics to every 60s")

    am = sub.add_parser("account-manager",
                        help="wallet + validator key tooling")
    am_sub = am.add_subparsers(dest="am_command", required=True)
    wc = am_sub.add_parser("wallet-create")
    wc.add_argument("--name", required=True)
    wc.add_argument("--password", required=True)
    wc.add_argument("--out", required=True, help="wallet JSON output path")
    wr = am_sub.add_parser("wallet-recover",
                           help="recover a wallet from a BIP-39 mnemonic")
    wr.add_argument("--name", required=True)
    wr.add_argument("--password", required=True)
    wr.add_argument("--mnemonic", required=True)
    wr.add_argument("--passphrase", default="")
    wr.add_argument("--out", required=True)
    vexit = am_sub.add_parser(
        "validator-exit", help="sign + publish a voluntary exit")
    vexit.add_argument("--keystore", required=True)
    vexit.add_argument("--password", required=True)
    vexit.add_argument("--validator-index", type=int, required=True)
    vexit.add_argument("--epoch", type=int, required=True)
    vexit.add_argument("--beacon-node", default="http://127.0.0.1:5052")
    vcreate = am_sub.add_parser("validator-create")
    vcreate.add_argument("--wallet", required=True)
    vcreate.add_argument("--wallet-password", required=True)
    vcreate.add_argument("--keystore-password", required=True)
    vcreate.add_argument("--count", type=int, default=1)
    vcreate.add_argument("--out-dir", required=True)

    vm = sub.add_parser("validator-manager",
                        help="bulk import/list validators")
    vm_sub = vm.add_subparsers(dest="vm_command", required=True)
    imp = vm_sub.add_parser("import")
    imp.add_argument("--keystores-dir", required=True)
    imp.add_argument("--password", required=True)
    imp.add_argument("--out", required=True,
                     help="validator_definitions.json output")
    vm_sub.add_parser("list").add_argument("--definitions", required=True)
    mv = vm_sub.add_parser(
        "move", help="move validators between VCs via their keymanager "
                     "APIs (delete+export from source, import to dest)")
    mv.add_argument("--src-url", required=True)
    mv.add_argument("--src-token", required=True)
    mv.add_argument("--dest-url", required=True)
    mv.add_argument("--dest-token", required=True)
    mv.add_argument("--pubkeys", required=True, nargs="+")
    mv.add_argument("--password", required=True,
                    help="transport password the moved keystores are "
                         "re-encrypted under")

    db = sub.add_parser("db", help="database inspection/maintenance")
    db_sub = db.add_subparsers(dest="db_command", required=True)
    db_sub.add_parser("inspect")
    db_sub.add_parser("compact")
    db_sub.add_parser("version")
    mig = db_sub.add_parser("migrate")
    mig.add_argument("--to", type=int, default=None,
                     help="target schema version (default: current)")
    prune = db_sub.add_parser("prune-states")
    prune.add_argument("--confirm", action="store_true")

    # lcli-equivalent dev tooling (reference lcli/src/{transition_blocks,
    # skip_slots,parse_ssz}.rs): timed state-transition runs over SSZ
    # fixtures — the CPU-baseline measuring stick.
    dev = sub.add_parser("dev", help="dev/benchmark tooling")
    dev_sub = dev.add_subparsers(dest="dev_command", required=True)
    tb = dev_sub.add_parser("transition-blocks",
                            help="apply block(s) to a pre-state, timed")
    tb.add_argument("--pre", required=True, help="pre-state SSZ path")
    tb.add_argument("--blocks", required=True, nargs="+",
                    help="signed-block SSZ path(s), in order")
    tb.add_argument("--fork", default="capella")
    tb.add_argument("--runs", type=_positive_int, default=1)
    tb.add_argument("--no-signature-verification", action="store_true")
    tb.add_argument("--post-out", default=None,
                    help="write the post-state SSZ here")
    sk = dev_sub.add_parser("skip-slots",
                            help="advance a pre-state N slots, timed")
    sk.add_argument("--pre", required=True)
    sk.add_argument("--slots", type=int, required=True)
    sk.add_argument("--fork", default="capella")
    sk.add_argument("--runs", type=_positive_int, default=1)
    sr = dev_sub.add_parser("state-root", help="hash_tree_root a state, timed")
    sr.add_argument("--state", required=True)
    sr.add_argument("--fork", default="capella")
    sr.add_argument("--runs", type=_positive_int, default=1)
    pz = dev_sub.add_parser("parse-ssz", help="decode an SSZ object to JSON")
    pz.add_argument("--type", required=True,
                    help="container name, e.g. SignedBeaconBlock:capella")
    pz.add_argument("path")
    return p


# -- subcommand drivers ------------------------------------------------------

def _run_bn(args) -> int:
    from lighthouse_tpu.client.builder import ClientBuilder, ClientConfig
    from lighthouse_tpu.common import compile_cache

    # before the first compile: the fused verify programs cost minutes
    # of XLA each, and a node that sets no persistent cache pays them on
    # every start unless the AOT store hits
    if args.bls_backend != "fake":
        compile_cache.configure()

    # one-shot routing calibration: measure host-vs-device pair-hash
    # rates and pick the merkle device thresholds for THIS host (the
    # static defaults assume a real TPU; an XLA-CPU fallback node would
    # route mid-sized trees to the slower path).  LHTPU_SHA_DEVICE_MIN
    # pins the threshold and skips the measurement.  Fake-crypto nodes
    # (process-fleet drills) skip it entirely: they never route device
    # work, and a fleet paying N calibration warmups serially would
    # blow its launch deadline
    if args.bls_backend != "fake":
        try:
            from lighthouse_tpu.ops import sha256 as _sha_ops

            _sha_ops.calibrate_device_thresholds()
        except Exception as e:
            # never block node startup on a calibration failure
            from lighthouse_tpu.common.metrics import record_swallowed

            record_swallowed("cli.sha_calibration", e)

    cfg = ClientConfig(
        network=args.network,
        network_config_path=args.network_config,
        datadir=args.datadir,
        http_port=args.http_port,
        execution_endpoint=args.execution_endpoint,
        execution_jwt_hex=args.execution_jwt,
        slasher_enabled=args.slasher,
        upnp_enabled=not args.disable_upnp and args.listen_port is not None,
        wire_transport=args.wire_transport,
        slasher_backend=args.slasher_backend,
        n_genesis_validators=args.interop_validators,
        genesis_fork=args.genesis_fork,
        genesis_time=args.genesis_time,
        bls_backend=args.bls_backend,
        listen_port=args.listen_port,
        boot_nodes=tuple(a.strip() for a in args.boot_nodes.split(",")
                         if a.strip()) if args.boot_nodes else (),
        builder_url=args.builder,
        trusted_setup_path=args.trusted_setup,
        monitoring_endpoint=args.monitoring_endpoint,
        seconds_per_slot=args.seconds_per_slot,
        identity_seed=args.identity_seed,
        interop_vc_range=(tuple(int(x) for x in args.interop_vc.split(":"))
                          if args.interop_vc else None),
    )

    # SIGTERM/SIGINT run the ORDERLY path — persist-frame + store close
    # + clean dirty-marker — so a fleet's stop() (SIGTERM) and kill()
    # (SIGKILL) have genuinely distinct on-disk semantics.  Installed
    # before the build: a TERM racing a slow assembly still lands
    import signal

    _stop_requested = threading.Event()
    _client_box: list = [None]

    def _graceful(signum, frame):
        _stop_requested.set()
        c = _client_box[0]
        if c is not None:
            c.executor.exit_event.set()

    for _sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(_sig, _graceful)
        except ValueError:
            # not the main thread (embedded use) — the KeyboardInterrupt
            # fallback below still covers interactive ^C
            break

    client = ClientBuilder(cfg).build()
    _client_box[0] = client
    if _stop_requested.is_set():
        client.executor.exit_event.set()
    wire = client.services.get("wire")
    print(json.dumps({
        "running": "bn", "network": client.spec.config_name,
        "http_port": client.http_server.port if client.http_server else None,
        "genesis_root": "0x" + client.chain.genesis_block_root.hex(),
        "wire_port": wire.listen_port if wire else None,
        "peer_id": wire.peer_id if wire else None,
    }), flush=True)
    try:
        deadline = (time.time() + args.run_seconds
                    if args.run_seconds else None)
        while deadline is None or time.time() < deadline:
            if client.executor.exit_event.wait(0.5):
                break
    except KeyboardInterrupt:
        pass
    client.stop()
    return 0


def _run_vc(args) -> int:
    import os

    from lighthouse_tpu.api import BeaconNodeClient
    from lighthouse_tpu.client.network_config import spec_for_network
    from lighthouse_tpu.crypto import keystore as ks
    from lighthouse_tpu.validator import ValidatorStore

    spec = spec_for_network(args.network)
    bn = BeaconNodeClient(args.beacon_node)
    genesis = bn.genesis()
    gvr = bytes.fromhex(genesis["genesis_validators_root"][2:])
    store = ValidatorStore(spec, gvr)
    if args.interop_range:
        from lighthouse_tpu.testing import interop_secret_key

        lo, hi = (int(x) for x in args.interop_range.split(":"))
        for i in range(lo, hi):
            store.add_validator(interop_secret_key(i), index=i)
    elif args.validators_dir:
        for name in sorted(os.listdir(args.validators_dir)):
            if not name.endswith(".json"):
                continue
            with open(os.path.join(args.validators_dir, name)) as f:
                store.import_keystore(json.load(f), args.keystore_password)
    print(json.dumps({
        "running": "vc", "validators": len(store.voting_pubkeys()),
        "beacon_node": args.beacon_node,
    }), flush=True)
    # standalone duty loop: the remote VC drives propose/attest per slot
    # over the standard HTTP API (validator/remote_client.py)
    from lighthouse_tpu.validator.remote_client import RemoteValidatorClient

    rvc = RemoteValidatorClient(bn, store, spec,
                                builder_blocks=args.builder_blocks)
    rvc.resolve_indices()
    mon = None
    mon_next = 0.0
    mon_thread = None
    if args.monitoring_endpoint:
        from lighthouse_tpu.common.system_health import MonitoringHttpClient

        mon = MonitoringHttpClient(args.monitoring_endpoint,
                                   validator_store=store)
    genesis_time = int(genesis["genesis_time"])
    deadline = time.time() + args.run_seconds if args.run_seconds else None
    last_slot = None
    while deadline is None or time.time() < deadline:
        now = time.time()
        if mon is not None and now >= mon_next and not (
                mon_thread is not None and mon_thread.is_alive()):
            # post off-thread: a dead endpoint's 5s timeout must never
            # delay slot duties (the bn path gets this from the
            # executor).  Runs pre-genesis too — operators want the VC
            # visible while it waits.
            import threading as _threading

            mon_thread = _threading.Thread(
                target=mon.send_metrics, args=(("validator", "system"),),
                daemon=True)
            mon_thread.start()
            mon_next = now + mon.update_period_s
        if now < genesis_time:
            # pre-genesis: wait without consuming slot 0, so slot-0
            # duties run when genesis actually arrives
            time.sleep(min(0.25, genesis_time - now))
            continue
        slot = int((now - genesis_time) // spec.seconds_per_slot)
        if slot != last_slot:
            last_slot = slot
            try:
                summary = rvc.run_slot(slot)
                print(json.dumps({
                    "slot": slot,
                    "proposed": summary.blocks_proposed,
                    "attested": summary.attestations_published,
                }), flush=True)
            except Exception as e:
                print(json.dumps({"slot": slot, "error": str(e)}),
                      flush=True)
        time.sleep(0.25)
    return 0


def _run_account_manager(args) -> int:
    from lighthouse_tpu.crypto.wallet import Wallet

    if args.am_command == "wallet-create":
        w = Wallet.create(args.name, args.password)
        with open(args.out, "w") as f:
            json.dump(w.data, f)
        print(json.dumps({"wallet": args.name, "path": args.out}))
        return 0
    if args.am_command == "wallet-recover":
        w = Wallet.recover(args.name, args.password, args.mnemonic,
                           args.passphrase)
        with open(args.out, "w") as f:
            json.dump(w.data, f)
        print(json.dumps({"wallet": args.name, "path": args.out,
                          "recovered": True}))
        return 0
    if args.am_command == "validator-exit":
        from lighthouse_tpu.api import BeaconNodeClient
        from lighthouse_tpu.client.network_config import spec_for_network
        from lighthouse_tpu.crypto import bls, keystore as ks
        from lighthouse_tpu import types as T
        from lighthouse_tpu.state_transition import misc

        with open(args.keystore) as f:
            keystore = json.load(f)
        sk = bls.SecretKey.from_bytes(ks.decrypt(keystore, args.password))
        bn = BeaconNodeClient(args.beacon_node)
        genesis = bn.genesis()
        gvr = bytes.fromhex(genesis["genesis_validators_root"][2:])
        spec = spec_for_network(args.network)
        exit_msg = T.VoluntaryExit(
            epoch=args.epoch, validator_index=args.validator_index)
        # the NODE verifies with the domain rule for ITS current fork
        # (signature_sets.voluntary_exit_set), so the signer must key off
        # the chain head, not the exit's epoch
        head = bn.header("head")
        head_slot = int(head["header"]["message"]["slot"])
        fork_now = spec.fork_at_epoch(
            spec.compute_epoch_at_slot(head_slot))
        if T.ChainSpec.fork_at_least(fork_now, "deneb"):
            version = spec.fork_version("capella")  # EIP-7044
        elif args.epoch < spec.fork_epoch(fork_now):
            # server get_domain: previous fork version for pre-boundary
            # epochs
            from lighthouse_tpu.types.spec import FORKS

            prev = FORKS[max(FORKS.index(fork_now) - 1, 0)]
            version = spec.fork_version(prev)
        else:
            version = spec.fork_version(fork_now)
        domain = misc.compute_domain(
            spec.domain_voluntary_exit, version, gvr)
        root = misc.compute_signing_root(exit_msg.hash_tree_root(), domain)
        signed = T.SignedVoluntaryExit(
            message=exit_msg, signature=sk.sign(root).to_bytes())
        bn._call("POST", "/eth/v1/beacon/pool/voluntary_exits",
                 {"ssz_hex": signed.serialize().hex()})
        print(json.dumps({"exit_published": args.validator_index,
                          "epoch": args.epoch}))
        return 0
    if args.am_command == "validator-create":
        import os

        with open(args.wallet) as f:
            w = Wallet(json.load(f))
        os.makedirs(args.out_dir, exist_ok=True)
        created = []
        for _ in range(args.count):
            keystore, _sk = w.next_validator(
                args.wallet_password, args.keystore_password)
            path = os.path.join(
                args.out_dir, f"keystore-{keystore['pubkey'][:16]}.json")
            with open(path, "w") as f:
                json.dump(keystore, f)
            created.append(keystore["pubkey"])
        with open(args.wallet, "w") as f:
            json.dump(w.data, f)  # persist nextaccount
        print(json.dumps({"created": created}))
        return 0
    raise SystemExit(f"unknown account-manager command {args.am_command}")


def _run_validator_manager(args) -> int:
    import os

    if args.vm_command == "import":
        from lighthouse_tpu.crypto import keystore as ks

        defs = []
        for name in sorted(os.listdir(args.keystores_dir)):
            if not name.endswith(".json"):
                continue
            path = os.path.join(args.keystores_dir, name)
            with open(path) as f:
                keystore = json.load(f)
            ks.decrypt(keystore, args.password)  # validate the password
            defs.append({
                "enabled": True,
                "voting_public_key": "0x" + keystore["pubkey"],
                "type": "local_keystore",
                "voting_keystore_path": path,
            })
        with open(args.out, "w") as f:
            json.dump(defs, f, indent=2)
        print(json.dumps({"imported": len(defs)}))
        return 0
    if args.vm_command == "move":
        import urllib.request

        def call(url, token, method, path, body=None):
            req = urllib.request.Request(
                url + path, method=method,
                data=json.dumps(body).encode() if body else None,
                headers={"Authorization": f"Bearer {token}",
                         "Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                return json.loads(resp.read())

        # 1. export from the source VC (keys re-encrypted + EIP-3076);
        # keep (pubkey, keystore) ALIGNED — a missing key must not shift
        # later pairings
        exported = call(args.src_url, args.src_token, "POST",
                        "/lighthouse/validators/export",
                        {"pubkeys": args.pubkeys,
                         "password": args.password})
        pairs = [(pk, k) for pk, k in zip(args.pubkeys, exported["data"])
                 if k is not None]
        if not pairs:
            raise SystemExit("no requested keys exist on the source VC")
        # 2. import to the destination VC with the slashing history
        imported = call(args.dest_url, args.dest_token, "POST",
                        "/eth/v1/keystores",
                        {"keystores": [k for _, k in pairs],
                         "passwords": [args.password] * len(pairs),
                         "slashing_protection":
                             exported["slashing_protection"]})
        # 3. delete from the source ONLY the keys the destination
        # confirmed — a failed import must never orphan a key
        confirmed = [pk for (pk, _), st_ in
                     zip(pairs, imported["data"])
                     if st_["status"] == "imported"]
        deleted = {"data": []}
        if confirmed:
            deleted = call(args.src_url, args.src_token, "DELETE",
                           "/eth/v1/keystores", {"pubkeys": confirmed})
        print(json.dumps({
            "moved": len(confirmed),
            "deleted": sum(1 for s_ in deleted["data"]
                           if s_["status"] == "deleted"),
            "failed": [st_ for st_ in imported["data"]
                       if st_["status"] != "imported"],
        }))
        return 0
    if args.vm_command == "list":
        with open(args.definitions) as f:
            defs = json.load(f)
        for d in defs:
            print(d["voting_public_key"])
        return 0
    raise SystemExit(f"unknown validator-manager command {args.vm_command}")


def _run_db(args) -> int:
    import os

    from lighthouse_tpu.store import NativeKVStore

    if not args.datadir:
        raise SystemExit("db commands need --datadir")

    if args.db_command in ("version", "migrate"):
        # open the raw KV only — HotColdDB would auto-migrate on open,
        # making 'version' destructive and 'migrate --to' uncontrollable
        from lighthouse_tpu.store import migrate_schema, read_schema_version

        hot_path = os.path.join(args.datadir, "hot.db")
        if not os.path.exists(hot_path):
            raise SystemExit(f"no database at {hot_path}")
        hot = NativeKVStore(hot_path)

        class _RawDB:  # the shim migrate_schema/read_schema_version need
            def __init__(self):
                self.hot = hot
                # prefer the DB's own recorded config; fall back to the
                # --network preset only for pre-v2 DBs that never stored
                # one (the operator must pass the right --network then)
                from lighthouse_tpu.store.migrations import read_db_config

                cfg = read_db_config(self)
                if cfg and "slots_per_restore_point" in cfg:
                    self.slots_per_restore_point = cfg[
                        "slots_per_restore_point"]
                else:
                    from lighthouse_tpu.client.network_config import (
                        spec_for_network,
                    )

                    spec = spec_for_network(args.network)
                    self.slots_per_restore_point = 2 * spec.slots_per_epoch

        db = _RawDB()
        if args.db_command == "migrate":
            v = migrate_schema(db, target=args.to)
        else:
            v = read_schema_version(db)
        hot.close()
        print(json.dumps({"schema_version": v}))
        return 0

    out = {}
    for name in ("hot.db", "cold.db"):
        path = os.path.join(args.datadir, name)
        if not os.path.exists(path):
            continue
        store = NativeKVStore(path)
        if args.db_command == "compact":
            store.compact()
        out[name] = {"keys": len(store),
                     "bytes": os.path.getsize(path)}
        store.close()
    if args.db_command == "prune-states" and not args.confirm:
        raise SystemExit("prune-states is destructive; pass --confirm")
    print(json.dumps({args.db_command: out}))
    return 0


def _run_dev(args) -> int:
    """lcli-equivalent timed tools (reference lcli/src/transition_blocks.rs
    :1-30 run/timing structure, skip_slots.rs)."""
    from lighthouse_tpu import types as T
    from lighthouse_tpu.client.network_config import spec_for_network

    spec = spec_for_network(args.network)
    t = T.make_types(spec.preset)

    def load_state(path, fork):
        with open(path, "rb") as f:
            return t.beacon_state_class(fork).deserialize(f.read())

    if args.dev_command == "parse-ssz":
        name, _, fork = args.type.partition(":")
        cls = (t.signed_beacon_block_class(fork or "capella")
               if name == "SignedBeaconBlock"
               else t.beacon_state_class(fork or "capella")
               if name == "BeaconState"
               else getattr(T, name))
        with open(args.path, "rb") as f:
            obj = cls.deserialize(f.read())
        root = obj.hash_tree_root()
        print(json.dumps({"type": args.type,
                          "hash_tree_root": "0x" + root.hex()}))
        return 0

    if args.dev_command == "state-root":
        state = load_state(args.state, args.fork)
        times = []
        for _ in range(args.runs):
            state_copy = state.copy()
            t0 = time.perf_counter()
            root = state_copy.hash_tree_root()
            times.append(time.perf_counter() - t0)
        print(json.dumps({
            "state_root": "0x" + root.hex(),
            "slot": int(state.slot),
            "ms_per_run": round(min(times) * 1000, 3)}))
        return 0

    if args.dev_command == "skip-slots":
        from lighthouse_tpu.state_transition import state_advance

        state = load_state(args.pre, args.fork)
        target = int(state.slot) + args.slots
        times = []
        for _ in range(args.runs):
            st = state.copy()
            t0 = time.perf_counter()
            state_advance(st, spec, target)
            times.append(time.perf_counter() - t0)
        print(json.dumps({
            "slots": args.slots,
            "post_root": "0x" + st.hash_tree_root().hex(),
            "ms_per_run": round(min(times) * 1000, 3)}))
        return 0

    if args.dev_command == "transition-blocks":
        from lighthouse_tpu.state_transition import (
            SignatureStrategy,
            state_transition,
        )

        state = load_state(args.pre, args.fork)
        blocks = []
        for path in args.blocks:
            with open(path, "rb") as f:
                blocks.append(
                    t.signed_beacon_block_class(args.fork).deserialize(
                        f.read()))
        strategy = (SignatureStrategy.NO_VERIFICATION
                    if args.no_signature_verification
                    else SignatureStrategy.VERIFY_BULK)
        times = []
        for _ in range(args.runs):
            st = state.copy()
            t0 = time.perf_counter()
            for blk in blocks:
                state_transition(st, spec, blk, strategy,
                                 validate_result=False)
            times.append(time.perf_counter() - t0)
        if args.post_out:
            with open(args.post_out, "wb") as f:
                f.write(st.serialize())
        print(json.dumps({
            "blocks": len(blocks),
            "post_root": "0x" + st.hash_tree_root().hex(),
            "ms_per_run": round(min(times) * 1000, 3)}))
        return 0
    raise SystemExit(f"unknown dev command {args.dev_command}")


def main(argv: list[str] | None = None) -> int:
    args = _build_parser().parse_args(argv)
    return {
        "bn": _run_bn,
        "vc": _run_vc,
        "account-manager": _run_account_manager,
        "validator-manager": _run_validator_manager,
        "db": _run_db,
        "dev": _run_dev,
    }[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
