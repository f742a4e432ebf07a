"""CLI — reference: the `grandine` binary crate (clap `GrandineArgs`,
grandine/src/grandine_args.rs:77,110-647; restart loop main.rs:101-123;
export/replay subcommands commands.rs).

Subcommands:
  run          in-process node on an interop genesis (devnet mode), with
               storage, HTTP API, metrics and the restart supervisor
  info         print resolved config/preset
  export / import-interchange   EIP-3076 slashing-protection data
  replay       re-validate a stored finalized chain from the database
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _bls_pubkey_arg(value: str) -> bytes:
    """argparse type: 48-byte hex BLS pubkey (rejects bad input at startup
    instead of bricking the builder path at proposal time)."""
    try:
        raw = bytes.fromhex(value.removeprefix("0x"))
    except ValueError:
        raise argparse.ArgumentTypeError(f"not hex: {value!r}")
    if len(raw) != 48:
        raise argparse.ArgumentTypeError(
            f"BLS pubkey must be 48 bytes, got {len(raw)}"
        )
    return raw


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="grandine-tpu",
        description="TPU-native Ethereum consensus framework",
    )
    parser.add_argument(
        "--network", default="minimal",
        help="named config: mainnet | minimal (default)")
    parser.add_argument(
        "--config-file", help="custom chain config YAML (consensus-specs format)")
    parser.add_argument("--data-dir", default="./grandine-tpu-data")
    parser.add_argument(
        "--features", default="",
        help="comma-separated runtime feature toggles")
    parser.add_argument(
        "--use-device", action="store_true",
        help="route batch verification through the TPU backend")
    parser.add_argument(
        "--devices", type=int, default=None, metavar="N",
        help="shard the verify plane over an N-device mesh (power of "
             "two; requires --use-device). On the CPU platform the "
             "visible device count comes from XLA_FLAGS="
             "--xla_force_host_platform_device_count=N, which XLA reads "
             "once at startup — set it in the environment BEFORE "
             "launching; --devices only selects from what is visible")
    parser.add_argument(
        "--no-warm", action="store_true",
        help="skip the startup precompile warmer. With --use-device, "
             "`run` warms before slot 1 what its firehose can dispatch: "
             "one batch bucket (a batch of any size, a single vote as a "
             "full batch, runs padded in it) per committee width: the "
             "head state's own width(s), or every width bucket up to the "
             "widest committee's for a networked node (--listen-port / "
             "--peer)")
    parser.add_argument(
        "--no-isolation", action="store_true",
        help="disable on-device fault localization of failed verify "
             "batches (falls back to recursive host bisection)")
    parser.add_argument(
        "--quarantine-exit-clean", type=int, default=None, metavar="K",
        help="consecutive clean quarantine batches before a suspect "
             "origin exits quarantine (default 3)")
    parser.add_argument(
        "--brownout", action=argparse.BooleanOptionalAction, default=True,
        help="adaptive overload control: a hysteretic brownout ladder "
             "sheds batching latency, admission headroom, and finally "
             "bulk work when the verify plane misses its SLOs "
             "(runtime/brownout.py; --no-brownout disables)")
    parser.add_argument(
        "--admission-max-share", type=float, default=None, metavar="F",
        help="fair-share admission cap: one gossip origin may hold at "
             "most this fraction of the verify plane's sliding window "
             "(default 0.5; origins under the absolute floor are never "
             "rejected)")

    sub = parser.add_subparsers(dest="command")

    run = sub.add_parser(
        "run", help="run an in-process devnet node",
        description="Run an in-process devnet node. With the global "
                    "--use-device the attestation firehose verifies on the "
                    "accelerator and its shapes are warmed before slot 1: "
                    "one batch bucket per committee width (see --no-warm).")
    run.add_argument("--validators", type=int, default=32)
    run.add_argument("--slots", type=int, default=32,
                     help="stop after this many slots (0 = run forever)")
    run.add_argument("--http-port", type=int, default=0,
                     help="serve the Beacon API on this port (0 = off)")
    run.add_argument("--no-restart", action="store_true",
                     help="disable the crash-restart supervisor")
    run.add_argument("--engine-url", default=None,
                     help="execution-engine JSON-RPC endpoint "
                          "(requires --jwt-secret)")
    run.add_argument("--jwt-secret", default=None,
                     help="path to the hex-encoded engine-API JWT secret")
    run.add_argument("--web3signer-url", default=None,
                     help="remote signer (Web3Signer REST) endpoint")
    run.add_argument("--checkpoint-sync-url", default=None,
                     help="Beacon API to checkpoint-sync the anchor state from")
    run.add_argument("--builder-url", default=None,
                     help="MEV builder relay endpoint")
    run.add_argument("--builder-pubkey", default=None, type=_bls_pubkey_arg,
                     help="pin the relay's BLS pubkey (96 hex chars); bids "
                          "signed by any other key are rejected")
    run.add_argument("--key-cache-password-file", default=None,
                     help="enable the encrypted validator key cache "
                          "(skips per-keystore KDF on restart)")
    run.add_argument("--keymanager-token-file", default=None,
                     help="bearer token required by the keymanager API "
                          "routes (unset = open)")
    run.add_argument("--metrics-url", default=None,
                     help="push client stats to this beaconcha.in-style "
                          "endpoint every 60s")
    run.add_argument("--trace-out", default=None,
                     help="append finished spans to this JSONL file (the "
                          "live ring buffer also serves "
                          "/eth/v1/debug/grandine/trace)")
    run.add_argument("--profile-dir", default=None,
                     help="root directory for on-demand device profile "
                          "captures (GET /eth/v1/debug/grandine/profile"
                          "?action=start); unset = annotation-only "
                          "capture sessions")
    run.add_argument("--profile-on-start", action="store_true",
                     help="open a profiler capture session at node start "
                          "(stop it via /eth/v1/debug/grandine/profile"
                          "?action=stop)")
    run.add_argument("--listen-port", type=int, default=None,
                     help="serve p2p (TCP gossip + req/resp) on this port "
                          "(0 = pick a free port)")
    run.add_argument("--peer", action="append", default=[],
                     help="host:port of a peer to dial (repeatable)")
    run.add_argument("--follow", action="store_true",
                     help="run no duties; range-sync + gossip-follow peers "
                          "until --until-finalized is reached")
    run.add_argument("--until-finalized", type=int, default=1,
                     help="--follow exits 0 once finalized epoch reaches this")
    run.add_argument("--follow-timeout", type=float, default=300.0)

    sub.add_parser("info", help="print the resolved configuration")

    exp = sub.add_parser("export-interchange",
                         help="export EIP-3076 slashing-protection data")
    exp.add_argument("output", help="output JSON path")

    imp = sub.add_parser("import-interchange",
                         help="import EIP-3076 slashing-protection data")
    imp.add_argument("input", help="input JSON path")

    rep = sub.add_parser("replay",
                         help="re-validate the stored finalized chain")
    rep.add_argument("--window", type=int, default=None,
                     help="blocks per cross-block verification batch")
    rep.add_argument("--per-block", action="store_true",
                     help="legacy one-dispatch-per-block replay (baseline)")
    rep.add_argument("--no-slasher", action="store_true",
                     help="skip historical slashing surveillance")
    return parser


def load_config(args):
    from grandine_tpu.types.config import Config

    if args.config_file:
        return Config.from_yaml(args.config_file)
    if args.network == "mainnet":
        return Config.mainnet()
    if args.network == "minimal":
        return Config.minimal()
    raise SystemExit(f"unknown network {args.network!r}")


def cmd_info(args) -> int:
    cfg = load_config(args)
    print(json.dumps({
        "config_name": cfg.config_name,
        "preset": cfg.preset_base,
        "slots_per_epoch": cfg.preset.SLOTS_PER_EPOCH,
        "seconds_per_slot": cfg.seconds_per_slot,
        "genesis_fork_version": "0x" + cfg.genesis_fork_version.hex(),
        "fork_epochs": {
            "altair": cfg.altair_fork_epoch,
            "bellatrix": cfg.bellatrix_fork_epoch,
            "capella": cfg.capella_fork_epoch,
            "deneb": cfg.deneb_fork_epoch,
        },
        "data_dir": args.data_dir,
    }, indent=2))
    return 0


def _node_once(args, cfg) -> int:
    """One node lifetime (the body inside the restart supervisor)."""
    from grandine_tpu.consensus.verifier import MultiVerifier, TpuVerifier
    from grandine_tpu.http_api import ApiContext, serve
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.pools import AttestationAggPool, OperationPool
    from grandine_tpu.runtime import Controller, InProcessNode
    from grandine_tpu.runtime.liveness import LivenessTracker
    from grandine_tpu.storage import Database, Storage
    from grandine_tpu.transition.genesis import interop_genesis_state

    os.makedirs(args.data_dir, exist_ok=True)
    db = Database.persistent(os.path.join(args.data_dir, "chain.sqlite"))
    storage = Storage(db, cfg)
    metrics = Metrics()
    from grandine_tpu.tracing import Tracer

    tracer = Tracer()
    if getattr(args, "trace_out", None):
        tracer.set_jsonl_path(args.trace_out)
        print(f"trace spans -> {args.trace_out}")

    # concrete HTTP clients behind the seams (http_clients.py); absent
    # flags keep the Null/Mock/injected defaults the tests use
    engine = None
    if getattr(args, "engine_url", None):
        from grandine_tpu.http_clients import EngineApiClient

        if not args.jwt_secret:
            raise SystemExit("--engine-url requires --jwt-secret")
        with open(args.jwt_secret) as f:
            secret = bytes.fromhex(f.read().strip().removeprefix("0x"))
        # transient EL failures retry with capped exponential backoff
        # (el_retry_total) instead of waiting for the next head
        engine = EngineApiClient(args.engine_url, secret).with_retries(
            metrics=metrics
        )

    if getattr(args, "checkpoint_sync_url", None) and (
        storage.load_anchor_state() is None
    ):
        # remote checkpoint only on FIRST start: a restart must resume from
        # the locally persisted anchor + unfinalized replay, not re-download
        # and discard local progress (reference StateLoadStrategy::Auto
        # prefers the local DB once one exists)
        from grandine_tpu.http_clients import checkpoint_fetcher
        from grandine_tpu.storage import StateLoadStrategy

        stored, unfinalized = storage.load(
            StateLoadStrategy.REMOTE,
            fetcher=checkpoint_fetcher(args.checkpoint_sync_url),
        )
    else:
        genesis = interop_genesis_state(args.validators, cfg)
        stored, unfinalized = storage.load(anchor_state=genesis)

    from grandine_tpu.slasher import Slasher

    operation_pool = OperationPool(cfg)
    slasher = Slasher(db, metrics=metrics)
    mesh = None
    if getattr(args, "devices", None):
        if not args.use_device:
            raise SystemExit("--devices requires --use-device")
        from grandine_tpu.tpu.mesh import VerifyMesh

        mesh = VerifyMesh.build(args.devices)
        print(f"verify mesh: {mesh.describe()}")
    node = InProcessNode(
        stored, cfg, use_device_firehose=args.use_device,
        execution_engine=engine,
        slasher=slasher, operation_pool=operation_pool,
        metrics=metrics, tracer=tracer,
        mesh=mesh,
        use_isolation=not getattr(args, "no_isolation", False),
        use_brownout=getattr(args, "brownout", True),
        database=db,
    )
    if getattr(args, "quarantine_exit_clean", None):
        node.reputation.exit_clean = max(1, args.quarantine_exit_clean)
    if getattr(args, "profile_dir", None):
        node.profiler.trace_root = args.profile_dir
        print(f"profile captures -> {args.profile_dir}")
    if getattr(args, "profile_on_start", False):
        node.profiler.start(note="cli --profile-on-start")
        # One-shot: the restart supervisor re-runs _node_once after a
        # crash, and on a saturated host the open trace can be what
        # starved the node — never re-open a session over the crashed
        # one (its global jax trace may still be running).
        args.profile_on_start = False
        print("profiler capture session open "
              "(GET /eth/v1/debug/grandine/profile?action=stop closes it)")
    if getattr(args, "admission_max_share", None):
        node.admission.max_share = args.admission_max_share
    if args.use_device and not getattr(args, "no_warm", False):
        _warm_firehose(
            node, cfg, metrics,
            networked=getattr(args, "listen_port", None) is not None
            or bool(getattr(args, "peer", None)),
        )
    if getattr(args, "web3signer_url", None):
        # remote-signer registry for a ValidatorService embedding; the
        # list_keys round-trip also fail-fasts on a bad endpoint
        from grandine_tpu.http_clients import Web3SignerClient
        from grandine_tpu.validator.signer import Signer

        client = Web3SignerClient(args.web3signer_url)
        remote_signer = Signer(web3signer=client)
        keys = client.list_keys()
        for pk_hex in keys:
            remote_signer.add_remote_key(bytes.fromhex(pk_hex))
        node.remote_signer = remote_signer
        print(f"web3signer: {len(keys)} remote keys at {args.web3signer_url}")
    if getattr(args, "builder_url", None):
        from grandine_tpu.builder_api import BuilderApi
        from grandine_tpu.http_clients import BuilderRelayClient

        node.builder_api = BuilderApi(
            BuilderRelayClient(args.builder_url), chain_config=cfg,
            relay_pubkey=getattr(args, "builder_pubkey", None),
        )
        print(f"builder relay: {args.builder_url}")
    node.controller.storage = storage
    node.controller.store.pre_prune_hook = node.controller._persist_finalized
    node.controller.metrics = metrics
    if getattr(args, "metrics_url", None):
        from grandine_tpu.metrics import RemoteMetricsService

        pusher = RemoteMetricsService(
            args.metrics_url, metrics, controller=node.controller,
            data_dir=args.data_dir,
        )
        pusher.start()
        print(f"metrics push: {args.metrics_url} every 60s")
    if unfinalized:
        # crash-restart: replay the persisted unfinalized head so we don't
        # regress to finality and double-propose already-signed slots
        from grandine_tpu.fork_choice.store import Tick, TickKind

        max_slot = max(int(b.message.slot) for b in unfinalized)
        node.controller.on_tick(Tick(max_slot, TickKind.AGGREGATE))
        for blk in unfinalized:
            node.controller.on_requested_block(blk)
        node.controller.wait()
        print(f"restored {len(unfinalized)} unfinalized blocks from storage")

    network = transport = None
    if getattr(args, "listen_port", None) is not None or getattr(args, "peer", None):
        from grandine_tpu.p2p.network import GossipTopics, Network
        from grandine_tpu.p2p.tcp import TcpTransport

        head_state = node.controller.snapshot().head_state
        transport = TcpTransport(
            peer_id=f"node-{os.getpid()}",
            fork_digest=GossipTopics.fork_digest(cfg, head_state),
            listen_port=args.listen_port or 0,
        )
        network = Network(
            transport, node.controller, cfg,
            attestation_verifier=node.attestation_verifier,
            storage=storage,
            operation_pool=operation_pool,
            verify_scheduler=node.verify_scheduler,
            admission=node.admission,
        )
        print(f"p2p listening on 127.0.0.1:{transport.port}", flush=True)
        for addr in args.peer:
            host, port = addr.rsplit(":", 1)
            pid = transport.connect(host, int(port))
            print(f"p2p connected to {pid} ({addr})", flush=True)

    server = None
    if args.http_port:
        from grandine_tpu.http_api.events import (
            EventBus,
            wire_controller_events,
        )
        from grandine_tpu.p2p.subnets import SubnetService
        from grandine_tpu.pools.sync_committee_pool import SyncCommitteeAggPool
        from grandine_tpu.validator.keymanager import KeyManager
        from grandine_tpu.validator.signer import Signer
        from grandine_tpu.validator.slashing_protection import (
            SlashingProtection,
        )

        bus = EventBus()
        wire_controller_events(node.controller, bus)
        # Keymanager backing registry: the Web3Signer-backed registry when
        # --web3signer-url is set, else a local-only Signer. NOTE: the
        # synthetic devnet driver (InProcessNode) signs duties with
        # interop keys; keys managed here drive a ValidatorService
        # embedding (validator/service.py), not the devnet loop — the
        # same split as the reference's validator-vs-node processes.
        km_signer = getattr(node, "remote_signer", None) or Signer()
        node.api_signer = km_signer
        key_cache = None
        if getattr(args, "key_cache_password_file", None):
            from grandine_tpu.validator.key_cache import (
                KeyCacheError,
                ValidatorKeyCache,
            )

            with open(args.key_cache_password_file) as f:
                key_cache = ValidatorKeyCache(
                    os.path.join(args.data_dir, "keys.cache"),
                    f.read().strip(),
                )
            try:
                n_cached = key_cache.load()  # fail fast on a wrong password
            except KeyCacheError as e:
                raise SystemExit(f"validator key cache: {e}")
            if n_cached:
                print(f"validator key cache: {n_cached} keys")
        km_token = None
        if getattr(args, "keymanager_token_file", None):
            with open(args.keymanager_token_file) as f:
                km_token = f.read().strip()
            if not km_token:
                # an empty token would silently DISABLE auth
                raise SystemExit(
                    f"--keymanager-token-file {args.keymanager_token_file} "
                    "is empty"
                )
        sync_pool = SyncCommitteeAggPool(cfg)
        if network is not None:
            network.sync_pool = sync_pool  # gossip sync topics feed it
        ctx = ApiContext(
            node.controller, cfg,
            attestation_pool=AttestationAggPool(cfg),
            operation_pool=operation_pool,
            liveness=LivenessTracker(args.validators),
            metrics=metrics,
            sync_pool=sync_pool,
            keymanager=KeyManager(
                km_signer,
                slashing_protection=SlashingProtection(db),
                key_cache=key_cache,
            ),
            event_bus=bus,
            network=network,
            subnet_service=SubnetService(cfg, network=network),
            keymanager_token=km_token,
            data_dir=args.data_dir,
            tracer=tracer,
            flight=node.flight,
            profiler=node.profiler,
        )
        server, _thread = serve(ctx, port=args.http_port)
        print(f"Beacon API on http://127.0.0.1:{args.http_port}")

    try:
        if getattr(args, "follow", False):
            return _follow_loop(args, node, transport)
        start = int(node.controller.snapshot().slot) + 1
        stop = start + args.slots if args.slots else None
        slot = start
        published = 0
        while stop is None or slot < stop:
            node.run_slot(slot)
            if network is not None:
                while published < len(node.produced_blocks):
                    network.publish_block(node.produced_blocks[published])
                    published += 1
            snap = node.head()
            print(
                f"slot {slot}: head={snap.head_root.hex()[:12]} "
                f"justified={int(snap.justified_checkpoint.epoch)} "
                f"finalized={int(snap.finalized_checkpoint.epoch)}",
                flush=True,
            )
            slot += 1
    finally:
        if transport is not None:
            transport.close()
        if server is not None:
            server.shutdown()
        node.stop()
        db.close()
    return 0


def _firehose_warm_plan(state, cfg, batch_bucket: int, networked: bool):
    """[(batch bucket, committee width)] — every shape the attestation
    firehose of THIS node can dispatch to the indexed aggregate kernel,
    read from the head state instead of the whole manifest (dozens of
    pairs, minutes of compile and gigabytes of host memory each when
    cold).

    The batch axis has ONE bucket: the verifier pads every call, a single
    vote as a full batch, into `AttestationVerifier.batch_bucket` (the
    kernel's time is flat in that axis). Only the member axis (widest
    committee in the batch) still has a ladder. A node without gossip
    ingress only ever sees its own duty loop — every committee of the
    slot a full aggregate — so it needs the committee-size bucket(s). A
    networked node can be handed anything from a single vote to a full
    aggregate: every width bucket up to the widest committee's."""
    from grandine_tpu.consensus import accessors
    from grandine_tpu.tpu.bls import _bucket

    p = cfg.preset
    epoch = accessors.get_current_epoch(state, p)
    active = len(accessors.get_active_validator_indices(state, epoch))
    per_slot = accessors.committee_count_per_slot(active, p)
    committees = p.SLOTS_PER_EPOCH * per_slot
    narrowest, widest = max(1, active // committees), -(-active // committees)
    if networked:
        widths = [w for w in (4 << i for i in range(16))
                  if w <= _bucket(widest)]
    else:
        widths = sorted({_bucket(narrowest), _bucket(widest)})
    return [(batch_bucket, w) for w in widths]


def _warm_firehose(node, cfg, metrics, networked: bool) -> None:
    """Compile what this node's device lanes can dispatch BEFORE the
    first slot: sequentially, on this thread, host memory trimmed after
    each entry (runtime/warmup.py). Today `cli run` builds one device
    lane, the attestation firehose over the resident pubkey registry.
    Not in the background: a compile beside the dispatch threads' own
    first compiles doubles a ~6 GB peak, and a slot that meets an
    uncompiled bucket stalls for the whole compile anyway."""
    from grandine_tpu.consensus import accessors
    from grandine_tpu.runtime.warmup import jit_cache_dir, warm_all

    verifier = node.attestation_verifier
    state = node.controller.snapshot().head_state
    plan = _firehose_warm_plan(state, cfg, verifier.batch_bucket, networked)
    widths = sorted({w for _, w in plan})
    print(
        f"[warmup] {len(plan)} entries: aggregate_idx batch bucket "
        f"{verifier.batch_bucket} x committee widths {widths}, one "
        f"at a time (cold: minutes each; cache {jit_cache_dir()})",
        flush=True,
    )
    if verifier.registry is not None:
        verifier.registry.ensure(accessors.registry_columns(state).pubkeys)
    for width in widths:
        warm_all(
            buckets=[("aggregate_idx", b) for b, w in plan if w == width],
            progress=lambda m: print(f"[warmup] {m}", flush=True),
            registry=verifier.registry,
            metrics=metrics,
            mesh=node.mesh,
            committee_width=width,
            seal=width == widths[-1],
        )


def _follow_loop(args, node, transport) -> int:
    """Dutiless follower: range-sync from peers (gossip rides alongside)
    until the finalized epoch reaches the target (two-process devnet)."""
    from grandine_tpu.p2p.sync import BlockSyncService

    if transport is None:
        raise SystemExit("--follow requires --peer/--listen-port")
    sync = BlockSyncService(transport, node.controller, node.cfg)
    deadline = time.time() + args.follow_timeout
    last_print = 0.0
    while time.time() < deadline:
        try:
            progress = sync.sync_once()
        except (ConnectionError, TimeoutError):
            progress = False
        snap = node.controller.snapshot()
        fin = int(snap.finalized_checkpoint.epoch)
        if time.time() - last_print > 1.0:
            print(
                f"follow: head_slot={int(snap.head_state.slot)} "
                f"finalized={fin} peers={len(transport.peers())}",
                flush=True,
            )
            last_print = time.time()
        if fin >= args.until_finalized:
            print(f"follow: finalized epoch {fin} reached", flush=True)
            return 0
        if not progress:
            time.sleep(0.25)
    print("follow: timeout before reaching finality target", file=sys.stderr)
    return 1


def cmd_run(args) -> int:
    """The restart supervisor (grandine/src/main.rs:101-123): a crash
    restarts the node from storage unless inhibited."""
    from grandine_tpu import features

    cfg = load_config(args)
    while True:
        try:
            return _node_once(args, cfg)
        except KeyboardInterrupt:
            return 130
        except Exception as e:
            if args.no_restart or features.is_enabled(
                features.Feature.INHIBIT_APPLICATION_RESTART
            ):
                raise
            print(f"node crashed ({e!r}); restarting from storage…",
                  file=sys.stderr)
            time.sleep(1)


def cmd_export_interchange(args) -> int:
    from grandine_tpu.storage import Database
    from grandine_tpu.validator.slashing_protection import SlashingProtection

    db = Database.persistent(
        os.path.join(args.data_dir, "slashing_protection.sqlite"))
    sp = SlashingProtection(db)
    with open(args.output, "w") as f:
        json.dump(sp.export_interchange(), f, indent=2)
    print(f"exported to {args.output}")
    return 0


def cmd_import_interchange(args) -> int:
    from grandine_tpu.storage import Database
    from grandine_tpu.validator.slashing_protection import SlashingProtection

    with open(args.input) as f:
        blob = json.load(f)
    gvr = bytes.fromhex(
        blob["metadata"]["genesis_validators_root"].removeprefix("0x"))
    db = Database.persistent(
        os.path.join(args.data_dir, "slashing_protection.sqlite"))
    sp = SlashingProtection(db, genesis_validators_root=gvr)
    sp.import_interchange(blob)
    print(f"imported {len(blob.get('data', []))} validator records")
    return 0


def cmd_replay(args) -> int:
    """Re-validate the stored finalized chain from its first anchor with
    cross-block batched signature verification, feeding every replayed
    attestation through the slasher (historical surveillance)."""
    from grandine_tpu.consensus.verifier import MultiVerifier, TpuVerifier
    from grandine_tpu.runtime.replay import (
        DEFAULT_WINDOW_BLOCKS,
        BulkReplayPipeline,
        ReplayInvalidBlock,
    )
    from grandine_tpu.slasher import Slasher
    from grandine_tpu.storage import Database, Storage
    from grandine_tpu.transition.combined import custom_state_transition

    cfg = load_config(args)
    db = Database.persistent(os.path.join(args.data_dir, "chain.sqlite"))
    storage = Storage(db, cfg)
    start_state = storage.load_genesis_state()
    if start_state is None:
        print("no stored chain", file=sys.stderr)
        return 1
    latest = storage.latest_persisted_slot()
    blocks = []
    for slot in range(int(start_state.slot) + 1, latest + 1):
        root = storage.finalized_root_by_slot(slot)
        if root is None:
            continue  # empty slot
        blocks.append(storage.finalized_block_by_root(root))
    t0 = time.time()
    if getattr(args, "per_block", False):
        cur = start_state
        for blk in blocks:
            verifier = TpuVerifier() if args.use_device else MultiVerifier()
            cur = custom_state_transition(cur, blk, cfg, verifier)
        n, sigsets, hits = len(blocks), 0, 0
    else:
        if getattr(args, "no_slasher", False):
            slasher = None
        elif args.use_device:
            # device replay: span updates for the window's solo
            # validators merge into one grid dispatch per window
            from grandine_tpu.tpu.spans import SpanPlane

            slasher = Slasher(span_plane=SpanPlane())
        else:
            slasher = Slasher()
        pipeline = BulkReplayPipeline(
            cfg, use_device=args.use_device,
            window_size=getattr(args, "window", None) or DEFAULT_WINDOW_BLOCKS,
            slasher=slasher,
        )
        try:
            pipeline.replay(start_state, blocks)
        except ReplayInvalidBlock as e:
            print(f"stored chain INVALID: {e}", file=sys.stderr)
            return 1
        n = pipeline.stats["blocks"]
        sigsets = pipeline.stats["sigsets"]
        hits = pipeline.stats["slasher_hits"]
    dt = time.time() - t0
    if n:
        print(f"replayed {n} blocks in {dt:.1f}s ({n / dt:.1f} blocks/s, "
              f"{sigsets} signature sets, {hits} slashing hit(s))")
    else:
        print("nothing to replay")
    return 0


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    from grandine_tpu import features

    for name in filter(None, args.features.split(",")):
        features.enable_by_name(name)
    commands = {
        "run": cmd_run,
        "info": cmd_info,
        "export-interchange": cmd_export_interchange,
        "import-interchange": cmd_import_interchange,
        "replay": cmd_replay,
    }
    if args.command is None:
        parser.print_help()
        return 2
    return commands[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
