"""Beacon API routes + dispatch — reference: http_api/src/routing.rs
(route table :221-234, states :341-369, pools :389-410), standard.rs
(handlers), http_api_utils (StateId/BlockId parsing).

The router is dependency-free: `(method, pattern)` pairs with `{param}`
segments; handlers take (ctx, params, query, body) and return JSON-able
dicts. `ApiContext` bundles the live services the handlers read.
"""

from __future__ import annotations

import json
import re
from typing import Any, Callable, Optional

from grandine_tpu import __version__
from grandine_tpu.consensus import accessors
from grandine_tpu.types.combined import state_phase_of
from grandine_tpu.types.primitives import FAR_FUTURE_EPOCH


class ApiError(Exception):
    def __init__(self, status: int, message: str) -> None:
        super().__init__(message)
        self.status = status
        self.message = message


class ApiContext:
    """What handlers see (reference http_api context): controller snapshot
    access plus the pools/trackers/metrics wired by the runtime."""

    def __init__(
        self,
        controller,
        cfg,
        attestation_pool=None,
        operation_pool=None,
        liveness=None,
        metrics=None,
        genesis_time: "Optional[int]" = None,
        keymanager=None,
        event_bus=None,
        validator_service=None,
        sync_pool=None,
        network=None,
        subnet_service=None,
        keymanager_token: "Optional[str]" = None,
        data_dir: "Optional[str]" = None,
        tracer=None,
        flight=None,
        profiler=None,
    ) -> None:
        self.controller = controller
        self.cfg = cfg
        self.attestation_pool = attestation_pool
        self.operation_pool = operation_pool
        self.liveness = liveness
        self.metrics = metrics
        self.genesis_time = genesis_time
        self.keymanager = keymanager
        self.event_bus = event_bus
        self.validator_service = validator_service
        self.sync_pool = sync_pool
        self.network = network
        self.subnet_service = subnet_service
        #: bearer token gating the keymanager routes at the server layer
        #: (server.py _authorized); None = open (in-process tests)
        self.keymanager_token = keymanager_token
        #: data directory whose on-disk size /metrics reports
        self.data_dir = data_dir
        #: grandine_tpu.tracing.Tracer backing /eth/v1/debug/grandine/trace
        self.tracer = tracer
        #: runtime.flight.FlightRecorder backing
        #: /eth/v1/debug/grandine/flight (verify-plane batch timeline)
        self.flight = flight
        #: runtime.profiler.KernelProfiler backing
        #: /eth/v1/debug/grandine/profile (device-time attribution +
        #: capture session control)
        self.profiler = profiler
        #: pubkey-hex -> SignedValidatorRegistrationV1 JSON (builder flow)
        self.validator_registrations: "dict[str, dict]" = {}
        #: validator index -> fee recipient (prepare_beacon_proposer)
        self.prepared_proposers: "dict[int, str]" = {}

    def snapshot(self):
        return self.controller.snapshot()

    def resolve_state(self, state_id: str):
        """StateId: head | finalized | justified | genesis | <slot> | <0xroot>."""
        snap = self.snapshot()
        if state_id == "head":
            return snap.head_state
        if state_id == "finalized":
            root = bytes(snap.finalized_checkpoint.root)
            node = self.controller.store.blocks.get(root)
            if node is not None:
                return node.state
            return snap.head_state  # anchor pruned: best effort
        if state_id == "justified":
            return self.controller.store.justified_state
        if state_id == "genesis":
            state_id = "0"
        if state_id.startswith("0x"):
            root = bytes.fromhex(state_id[2:])
            for node in self.controller.store.blocks.values():
                if node.state.hash_tree_root() == root:
                    return node.state
            raise ApiError(404, f"state {state_id} not found")
        try:
            slot = int(state_id)
        except ValueError:
            raise ApiError(400, f"invalid state id {state_id!r}") from None
        for node in sorted(
            self.controller.store.blocks.values(), key=lambda n: n.slot
        ):
            if node.slot == slot:
                return node.state
        raise ApiError(404, f"no state at slot {slot}")

    def resolve_block(self, block_id: str):
        snap = self.snapshot()
        store = self.controller.store
        if block_id == "head":
            return store.blocks[snap.head_root]
        if block_id == "finalized":
            node = store.blocks.get(bytes(snap.finalized_checkpoint.root))
            if node is None:
                raise ApiError(404, "finalized block pruned")
            return node
        if block_id.startswith("0x"):
            node = store.blocks.get(bytes.fromhex(block_id[2:]))
            if node is None:
                raise ApiError(404, f"block {block_id} not found")
            return node
        try:
            slot = int(block_id)
        except ValueError:
            raise ApiError(400, f"invalid block id {block_id!r}") from None
        for node in store.blocks.values():
            if node.slot == slot:
                return node
        raise ApiError(404, f"no block at slot {slot}")


def hex_(b: bytes) -> str:
    return "0x" + bytes(b).hex()


def _parse_int(value, what: str) -> int:
    try:
        return int(value)
    except (TypeError, ValueError):
        raise ApiError(400, f"invalid {what}: {value!r}") from None


# ------------------------------------------------------------------ router


class Router:
    def __init__(self) -> None:
        self.routes: "list[tuple[str, re.Pattern, Callable]]" = []

    def add(self, method: str, pattern: str, handler: Callable) -> None:
        regex = re.compile(
            "^" + re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern) + "$"
        )
        self.routes.append((method.upper(), regex, handler))

    def dispatch(
        self, ctx: ApiContext, method: str, path: str,
        query: "Optional[dict]" = None, body: Any = None,
    ):
        """Returns (status, payload). JSON endpoints return dicts; /metrics
        returns text."""
        for m, regex, handler in self.routes:
            if m != method.upper():
                continue
            match = regex.match(path)
            if match is None:
                continue
            try:
                payload = handler(ctx, match.groupdict(), query or {}, body)
                return 200, payload
            except ApiError as e:
                return e.status, {"code": e.status, "message": e.message}
            except Exception as e:  # handler crash -> 500, not a dead server
                return 500, {"code": 500, "message": repr(e)}
        return 404, {"code": 404, "message": f"no route for {method} {path}"}


# ---------------------------------------------------------------- handlers


def get_node_version(ctx, params, query, body):
    return {"data": {"version": f"grandine-tpu/{__version__}"}}


def get_node_health(ctx, params, query, body):
    return {}


def get_node_syncing(ctx, params, query, body):
    snap = ctx.snapshot()
    head_slot = int(snap.head_state.slot)
    return {
        "data": {
            "head_slot": str(head_slot),
            "sync_distance": str(max(0, snap.slot - head_slot)),
            "is_syncing": snap.slot - head_slot > 1,
            "is_optimistic": bool(getattr(snap, "is_optimistic", False)),
            "el_offline": True,
        }
    }


def get_genesis(ctx, params, query, body):
    snap = ctx.snapshot()
    state = snap.head_state
    return {
        "data": {
            "genesis_time": str(int(state.genesis_time)),
            "genesis_validators_root": hex_(state.genesis_validators_root),
            "genesis_fork_version": hex_(ctx.cfg.genesis_fork_version),
        }
    }


def get_state_root(ctx, params, query, body):
    state = ctx.resolve_state(params["state_id"])
    return {"data": {"root": hex_(state.hash_tree_root())}}


def get_debug_fork_choice(ctx, params, query, body):
    """Beacon API /eth/v1/debug/fork_choice (http_api/src/routing.rs:461):
    the store's block DAG with per-node weight/viability detail.

    The DAG is mutator-owned; this handler reads it racily (blocks is
    insert-only except at finality/invalidation pruning) and retries the
    whole computation on a concurrent-mutation error instead of taking a
    lock on the hot path — a debug endpoint must never slow the mutator."""
    store = ctx.controller.store
    snap = ctx.snapshot()
    last_err = None
    for _attempt in range(3):
        try:
            return _debug_fork_choice_once(store, snap)
        except RuntimeError as e:  # dict mutated during iteration
            last_err = e
    raise last_err


def _debug_fork_choice_once(store, snap):
    weights = store._subtree_weights(bytes(store.justified_checkpoint.root))
    nodes = []
    for root, node in list(store.blocks.items()):
        nodes.append({
            "slot": str(node.slot),
            "block_root": hex_(root),
            "parent_root": hex_(node.parent_root),
            "justified_epoch": str(
                int(node.state.current_justified_checkpoint.epoch)
            ),
            "finalized_epoch": str(int(node.state.finalized_checkpoint.epoch)),
            "weight": str(weights.get(root, 0)),
            "validity": "optimistic" if node.optimistic else "valid",
            "execution_block_hash": hex_(
                node.execution_block_hash or b"\x00" * 32
            ),
        })
    return {
        "justified_checkpoint": {
            "epoch": str(int(snap.justified_checkpoint.epoch)),
            "root": hex_(snap.justified_checkpoint.root),
        },
        "finalized_checkpoint": {
            "epoch": str(int(snap.finalized_checkpoint.epoch)),
            "root": hex_(snap.finalized_checkpoint.root),
        },
        "fork_choice_nodes": nodes,
    }


def get_debug_heads(ctx, params, query, body):
    """Chain tips (blocks without children) — /eth/v2/debug/beacon/heads.
    Same racy-read + snapshot-copy discipline as debug_fork_choice."""
    store = ctx.controller.store
    snap = ctx.snapshot()
    blocks = dict(store.blocks)
    children = dict(store.children)
    heads = [
        {
            "root": hex_(root),
            "slot": str(node.slot),
            "execution_optimistic": bool(node.optimistic),
        }
        for root, node in blocks.items()
        if not children.get(root)
    ]
    return {"data": heads or [{
        "root": hex_(snap.head_root),
        "slot": str(int(snap.head_state.slot)),
        "execution_optimistic": bool(snap.is_optimistic),
    }]}


def get_debug_state(ctx, params, query, body):
    """Full SSZ state dump — /eth/v2/debug/beacon/states/{state_id}
    (returns the raw container; the server layer SSZ/JSON-encodes)."""
    from grandine_tpu.types.combined import state_phase_of

    state = ctx.resolve_state(params["state_id"])
    return {
        "version": state_phase_of(state, ctx.cfg).key,
        "execution_optimistic": bool(
            getattr(ctx.snapshot(), "is_optimistic", False)
        ),
        "data": {"ssz": "0x" + state.serialize().hex()},
    }


def get_state_fork(ctx, params, query, body):
    state = ctx.resolve_state(params["state_id"])
    return {
        "data": {
            "previous_version": hex_(state.fork.previous_version),
            "current_version": hex_(state.fork.current_version),
            "epoch": str(int(state.fork.epoch)),
        }
    }


def get_finality_checkpoints(ctx, params, query, body):
    state = ctx.resolve_state(params["state_id"])

    def cp(c):
        return {"epoch": str(int(c.epoch)), "root": hex_(c.root)}

    return {
        "data": {
            "previous_justified": cp(state.previous_justified_checkpoint),
            "current_justified": cp(state.current_justified_checkpoint),
            "finalized": cp(state.finalized_checkpoint),
        }
    }


def _validator_status(v, balance: int, epoch: int) -> str:
    if int(v.activation_epoch) > epoch:
        return (
            "pending_queued"
            if int(v.activation_eligibility_epoch) != FAR_FUTURE_EPOCH
            else "pending_initialized"
        )
    if epoch < int(v.exit_epoch):
        return "active_slashed" if bool(v.slashed) else "active_ongoing"
    if epoch < int(v.withdrawable_epoch):
        return "exited_slashed" if bool(v.slashed) else "exited_unslashed"
    return "withdrawal_done" if balance == 0 else "withdrawal_possible"


def get_state_validators(ctx, params, query, body):
    state = ctx.resolve_state(params["state_id"])
    p = ctx.cfg.preset
    epoch = accessors.get_current_epoch(state, p)
    ids = query.get("id")
    if ids:
        try:
            indices = [int(i) for i in ids.split(",")]
        except ValueError:
            raise ApiError(400, f"invalid validator id list {ids!r}") from None
        if any(i < 0 for i in indices):
            raise ApiError(400, "validator indices must be non-negative")
    else:
        indices = range(len(state.validators))
    rows = []
    for i in indices:
        if i >= len(state.validators):
            continue
        v = state.validators[i]
        balance = int(state.balances[i])
        rows.append({
            "index": str(i),
            "balance": str(balance),
            "status": _validator_status(v, balance, epoch),
            "validator": {
                "pubkey": hex_(v.pubkey),
                "withdrawal_credentials": hex_(v.withdrawal_credentials),
                "effective_balance": str(int(v.effective_balance)),
                "slashed": bool(v.slashed),
                "activation_eligibility_epoch": str(int(v.activation_eligibility_epoch)),
                "activation_epoch": str(int(v.activation_epoch)),
                "exit_epoch": str(int(v.exit_epoch)),
                "withdrawable_epoch": str(int(v.withdrawable_epoch)),
            },
        })
    return {"execution_optimistic": False, "finalized": False, "data": rows}


def get_block(ctx, params, query, body):
    node = ctx.resolve_block(params["block_id"])
    signed = node.signed_block
    state = ctx.snapshot().head_state
    version = state_phase_of(node.state, ctx.cfg).key
    message = getattr(signed, "message", None)
    if message is None or not hasattr(signed, "serialize"):
        raise ApiError(404, "anchor block body unavailable")
    return {
        "version": version,
        "execution_optimistic": False,
        "finalized": node.slot
        <= int(ctx.snapshot().finalized_checkpoint.epoch)
        * ctx.cfg.preset.SLOTS_PER_EPOCH,
        "data": {"message_root": hex_(message.hash_tree_root()),
                 "slot": str(node.slot),
                 "proposer_index": str(int(message.proposer_index)),
                 "ssz": hex_(signed.serialize())},
    }


def get_block_root(ctx, params, query, body):
    node = ctx.resolve_block(params["block_id"])
    return {"data": {"root": hex_(node.root)}}


def get_headers(ctx, params, query, body):
    snap = ctx.snapshot()
    node = ctx.controller.store.blocks[snap.head_root]
    return {
        "data": [{
            "root": hex_(node.root),
            "canonical": True,
            "header": {
                "message": {
                    "slot": str(node.slot),
                    "parent_root": hex_(node.parent_root),
                    "state_root": hex_(node.state.hash_tree_root()),
                },
            },
        }]
    }


def post_pool_attestations(ctx, params, query, body):
    if ctx.attestation_pool is None:
        raise ApiError(503, "attestation pool not wired")
    from grandine_tpu.types.combined import fork_namespace
    from grandine_tpu.types.primitives import Phase

    failures = []
    for i, att_json in enumerate(body or []):
        try:
            att = _attestation_from_json(ctx, att_json)
            ctx.attestation_pool.insert(att)
            if ctx.event_bus is not None:
                ctx.event_bus.publish("attestation", att_json)
        except Exception as e:
            failures.append({"index": i, "message": repr(e)})
    if failures:
        raise ApiError(400, json.dumps(failures))
    return {}


def _attestation_from_json(ctx, j):
    ns = _ns_of_head(ctx)
    bits_type = _field_type(ns.Attestation, "aggregation_bits")
    bits = bits_type.deserialize(_b(j["aggregation_bits"]))
    return ns.Attestation(
        aggregation_bits=bits,
        data=_json_to_attestation_data(ns, j["data"]),
        signature=_b(j["signature"], 96),
    )


def get_pool_voluntary_exits(ctx, params, query, body):
    if ctx.operation_pool is None:
        raise ApiError(503, "operation pool not wired")
    exits = ctx.operation_pool.contents()["voluntary_exits"]
    return {
        "data": [
            {
                "message": {
                    "epoch": str(int(e.message.epoch)),
                    "validator_index": str(int(e.message.validator_index)),
                },
                "signature": hex_(e.signature),
            }
            for e in exits
        ]
    }


def get_config_spec(ctx, params, query, body):
    cfg = ctx.cfg
    p = cfg.preset
    data = {
        "PRESET_BASE": cfg.preset_base,
        "CONFIG_NAME": cfg.config_name,
        "SECONDS_PER_SLOT": str(cfg.seconds_per_slot),
        "SLOTS_PER_EPOCH": str(p.SLOTS_PER_EPOCH),
        "GENESIS_FORK_VERSION": hex_(cfg.genesis_fork_version),
        "ALTAIR_FORK_EPOCH": str(cfg.altair_fork_epoch),
        "BELLATRIX_FORK_EPOCH": str(cfg.bellatrix_fork_epoch),
        "CAPELLA_FORK_EPOCH": str(cfg.capella_fork_epoch),
        "DENEB_FORK_EPOCH": str(cfg.deneb_fork_epoch),
        "MAX_EFFECTIVE_BALANCE": str(p.MAX_EFFECTIVE_BALANCE),
        "MIN_ATTESTATION_INCLUSION_DELAY": str(p.MIN_ATTESTATION_INCLUSION_DELAY),
        "DEPOSIT_CONTRACT_ADDRESS": hex_(cfg.deposit_contract_address),
        "DEPOSIT_CHAIN_ID": str(cfg.deposit_chain_id),
    }
    return {"data": data}


def get_deposit_contract(ctx, params, query, body):
    return {
        "data": {
            "chain_id": str(ctx.cfg.deposit_chain_id),
            "address": hex_(ctx.cfg.deposit_contract_address),
        }
    }


def get_proposer_duties(ctx, params, query, body):
    """eth/v1/validator/duties/proposer/{epoch}: proposer per slot of the
    epoch. One in-epoch state suffices — the proposer seed mixes the slot
    into the epoch's RANDAO-derived seed (misc.proposer_seed), so all
    SLOTS_PER_EPOCH proposers come from per-slot seeds over one shuffle."""
    from grandine_tpu.consensus import misc

    p = ctx.cfg.preset
    epoch = _parse_int(params["epoch"], "epoch")
    snap = ctx.snapshot()
    state = snap.head_state
    cur = accessors.get_current_epoch(state, p)
    if epoch > cur + 1:
        raise ApiError(400, f"epoch {epoch} beyond the lookahead window")
    start = misc.compute_start_slot_at_epoch(epoch, p)
    if epoch > cur:  # advance into the epoch (StateCache memoizes)
        state = ctx.controller.state_at_slot(start)
    cols = accessors.registry_columns(state)
    active = cols.active_indices(epoch)
    duties = []
    for slot in range(start, start + p.SLOTS_PER_EPOCH):
        seed = misc.proposer_seed(state, slot, p)
        index = misc.compute_proposer_index(
            cols.effective_balance, active, seed, p
        )
        duties.append({
            "pubkey": hex_(cols.pubkeys[index]),
            "validator_index": str(index),
            "slot": str(slot),
        })
    return {"dependent_root": hex_(snap.head_root), "data": duties}


def post_attester_duties(ctx, params, query, body):
    """eth/v1/validator/duties/attester/{epoch} for the posted indices."""
    from grandine_tpu.consensus import misc

    p = ctx.cfg.preset
    epoch = _parse_int(params["epoch"], "epoch")
    snap = ctx.snapshot()
    state = snap.head_state
    cur = accessors.get_current_epoch(state, p)
    if epoch > cur + 1:
        raise ApiError(400, f"epoch {epoch} beyond the lookahead window")
    want = {_parse_int(i, "validator index") for i in (body or [])}
    if not want:
        # Beacon API contract: duties only for the POSTED indices
        return {"dependent_root": hex_(snap.head_root), "data": []}
    cols = accessors.registry_columns(state)
    duties = []
    start = misc.compute_start_slot_at_epoch(epoch, p)
    count = accessors.get_committee_count_per_slot(state, epoch, p)
    for slot in range(start, start + p.SLOTS_PER_EPOCH):
        for index in range(count):
            committee = accessors.get_beacon_committee(state, slot, index, p)
            for pos, vi in enumerate(committee):
                vi = int(vi)
                if vi not in want:
                    continue
                duties.append({
                    "pubkey": hex_(cols.pubkeys[vi]),
                    "validator_index": str(vi),
                    "committee_index": str(index),
                    "committee_length": str(len(committee)),
                    "committees_at_slot": str(count),
                    "validator_committee_index": str(pos),
                    "slot": str(slot),
                })
    return {"dependent_root": hex_(snap.head_root), "data": duties}


def post_validator_liveness(ctx, params, query, body):
    if ctx.liveness is None:
        raise ApiError(503, "liveness tracker not wired")
    epoch = int(params["epoch"])
    indices = [int(i) for i in (body or [])]
    return {"data": ctx.liveness.liveness(epoch, indices)}


def get_metrics(ctx, params, query, body):
    if ctx.metrics is None:
        raise ApiError(503, "metrics not wired")
    ctx.metrics.collect_system_stats(ctx.data_dir)
    return ctx.metrics.expose()  # text payload


def get_debug_trace(ctx, params, query, body):
    """Chrome trace-event dump of the tracer's span ring buffer — load
    the payload in chrome://tracing or Perfetto. `?clear=true` drains
    the buffer after the dump so successive captures don't overlap."""
    if ctx.tracer is None:
        raise ApiError(503, "tracer not wired")
    payload = ctx.tracer.chrome_trace()
    if str(query.get("clear", "")).lower() in ("1", "true", "yes"):
        ctx.tracer.clear()
    return payload


def get_debug_flight(ctx, params, query, body):
    """Verify-plane flight-recorder dump: the newest batch/canary/breaker
    records plus the aggregate summary (SLO misses by lane+cause, bucket
    fill, duty cycle, top failing origins). `?lane=` filters to one lane,
    `?kind=` to one record kind, `?n=` bounds the record count."""
    if ctx.flight is None:
        raise ApiError(503, "flight recorder not wired")
    lane = query.get("lane") or None
    kind = query.get("kind") or None
    try:
        n = int(query.get("n", 256))
    except ValueError:
        raise ApiError(400, "n must be an integer") from None
    if n < 0:
        raise ApiError(400, "n must be non-negative")
    records = ctx.flight.snapshot(lane=lane, n=n, kind=kind)
    return {
        "data": {
            "records": [r.as_dict() for r in records],
            "summary": ctx.flight.summary(),
            "slo": ctx.flight.slo_misses(),
            "origins": ctx.flight.origins.snapshot(),
        }
    }


def get_debug_profile(ctx, params, query, body):
    """Kernel-profiler view + capture control. Default GET serves the
    always-on device timeline (per-kernel busy seconds, idle seconds by
    cause, dispatch counts, the finished-session ring, HBM family
    bytes); `?kernel=` / `?scheme=` filter the busy rows,
    `?n=` bounds the session list. `?action=start[&trace_dir=...]`
    opens a capture session (409 when one is active), `?action=stop`
    closes it and returns the finished session record."""
    if ctx.profiler is None:
        raise ApiError(503, "profiler not wired")
    action = str(query.get("action", "")).lower()
    if action == "start":
        trace_dir = query.get("trace_dir") or None
        try:
            sess = ctx.profiler.start(trace_dir=trace_dir)
        except RuntimeError as exc:
            raise ApiError(409, str(exc)) from None
        return {"data": {"session": sess}}
    if action == "stop":
        try:
            sess = ctx.profiler.stop()
        except RuntimeError as exc:
            raise ApiError(409, str(exc)) from None
        return {"data": {"session": sess}}
    if action:
        raise ApiError(400, "action must be start or stop")
    kernel = query.get("kernel") or None
    scheme = query.get("scheme") or None
    try:
        n = int(query.get("n", 32))
    except ValueError:
        raise ApiError(400, "n must be an integer") from None
    if n < 0:
        raise ApiError(400, "n must be non-negative")
    return {
        "data": ctx.profiler.summary(
            kernel=kernel, scheme=scheme, n_sessions=n
        )
    }


# ------------------------------------------- JSON <-> container codecs
# (the reference serializes via serde; these hand-rolled converters cover
# the Beacon API pool/validator payloads)


def _ns_of_head(ctx):
    from grandine_tpu.types.combined import fork_namespace

    snap = ctx.snapshot()
    phase = state_phase_of(snap.head_state, ctx.cfg)
    return fork_namespace(ctx.cfg, phase)


def _b(hexstr: str, length: "Optional[int]" = None) -> bytes:
    raw = bytes.fromhex(hexstr.removeprefix("0x"))
    if length is not None and len(raw) != length:
        raise ApiError(400, f"expected {length} bytes, got {len(raw)}")
    return raw


def _json_to_attestation_data(ns, d):
    return ns.AttestationData(
        slot=int(d["slot"]),
        index=int(d["index"]),
        beacon_block_root=_b(d["beacon_block_root"], 32),
        source=ns.Checkpoint(
            epoch=int(d["source"]["epoch"]), root=_b(d["source"]["root"], 32)
        ),
        target=ns.Checkpoint(
            epoch=int(d["target"]["epoch"]), root=_b(d["target"]["root"], 32)
        ),
    )


def _attestation_data_to_json(d) -> dict:
    return {
        "slot": str(int(d.slot)),
        "index": str(int(d.index)),
        "beacon_block_root": hex_(d.beacon_block_root),
        "source": {
            "epoch": str(int(d.source.epoch)),
            "root": hex_(d.source.root),
        },
        "target": {
            "epoch": str(int(d.target.epoch)),
            "root": hex_(d.target.root),
        },
    }


def _field_type(container, name: str):
    cls = container if isinstance(container, type) else type(container)
    for n, t in cls.FIELDS:
        if n == name:
            return t
    raise KeyError(name)


def _attestation_to_json(att) -> dict:
    bits_type = _field_type(att, "aggregation_bits")
    return {
        "aggregation_bits": hex_(bits_type.serialize(att.aggregation_bits)),
        "data": _attestation_data_to_json(att.data),
        "signature": hex_(att.signature),
    }


def _json_to_indexed_attestation(ns, j):
    return ns.IndexedAttestation(
        attesting_indices=[int(i) for i in j["attesting_indices"]],
        data=_json_to_attestation_data(ns, j["data"]),
        signature=_b(j["signature"], 96),
    )


def _indexed_attestation_to_json(a) -> dict:
    return {
        "attesting_indices": [str(int(i)) for i in a.attesting_indices],
        "data": _attestation_data_to_json(a.data),
        "signature": hex_(a.signature),
    }


def _json_to_signed_header(ns, j):
    m = j["message"]
    return ns.SignedBeaconBlockHeader(
        message=ns.BeaconBlockHeader(
            slot=int(m["slot"]),
            proposer_index=int(m["proposer_index"]),
            parent_root=_b(m["parent_root"], 32),
            state_root=_b(m["state_root"], 32),
            body_root=_b(m["body_root"], 32),
        ),
        signature=_b(j["signature"], 96),
    )


def _signed_header_to_json(h) -> dict:
    return {
        "message": {
            "slot": str(int(h.message.slot)),
            "proposer_index": str(int(h.message.proposer_index)),
            "parent_root": hex_(h.message.parent_root),
            "state_root": hex_(h.message.state_root),
            "body_root": hex_(h.message.body_root),
        },
        "signature": hex_(h.signature),
    }


# -------------------------------------------------- pool breadth handlers
# reference: http_api/src/routing.rs:389-410 (pool GET/POST per op type)


def _require_op_pool(ctx):
    if ctx.operation_pool is None:
        raise ApiError(503, "operation pool not wired")
    return ctx.operation_pool


def get_pool_attestations(ctx, params, query, body):
    if ctx.attestation_pool is None:
        raise ApiError(503, "attestation pool not wired")
    atts = ctx.attestation_pool.all_attestations()
    slot = query.get("slot")
    if slot is not None:
        atts = [a for a in atts if int(a.data.slot) == int(slot)]
    index = query.get("committee_index")
    if index is not None:
        atts = [a for a in atts if int(a.data.index) == int(index)]
    return {"data": [_attestation_to_json(a) for a in atts]}


def post_pool_voluntary_exits(ctx, params, query, body):
    pool = _require_op_pool(ctx)
    ns = _ns_of_head(ctx)
    j = body or {}
    try:
        exit_ = ns.SignedVoluntaryExit(
            message=ns.VoluntaryExit(
                epoch=int(j["message"]["epoch"]),
                validator_index=int(j["message"]["validator_index"]),
            ),
            signature=_b(j["signature"], 96),
        )
    except (KeyError, ValueError, TypeError) as e:
        raise ApiError(400, f"malformed voluntary exit: {e!r}") from None
    pool.insert_voluntary_exit(exit_)
    if ctx.event_bus is not None:
        ctx.event_bus.publish("voluntary_exit", j)
    return {}


def get_pool_proposer_slashings(ctx, params, query, body):
    ops = _require_op_pool(ctx).contents()["proposer_slashings"]
    return {
        "data": [
            {
                "signed_header_1": _signed_header_to_json(s.signed_header_1),
                "signed_header_2": _signed_header_to_json(s.signed_header_2),
            }
            for s in ops
        ]
    }


def post_pool_proposer_slashings(ctx, params, query, body):
    pool = _require_op_pool(ctx)
    ns = _ns_of_head(ctx)
    j = body or {}
    try:
        slashing = ns.ProposerSlashing(
            signed_header_1=_json_to_signed_header(ns, j["signed_header_1"]),
            signed_header_2=_json_to_signed_header(ns, j["signed_header_2"]),
        )
    except (KeyError, ValueError, TypeError) as e:
        raise ApiError(400, f"malformed proposer slashing: {e!r}") from None
    pool.insert_proposer_slashing(slashing)
    if ctx.event_bus is not None:
        ctx.event_bus.publish("proposer_slashing", j)
    return {}


def get_pool_attester_slashings(ctx, params, query, body):
    ops = _require_op_pool(ctx).contents()["attester_slashings"]
    return {
        "data": [
            {
                "attestation_1": _indexed_attestation_to_json(s.attestation_1),
                "attestation_2": _indexed_attestation_to_json(s.attestation_2),
            }
            for s in ops
        ]
    }


def post_pool_attester_slashings(ctx, params, query, body):
    pool = _require_op_pool(ctx)
    ns = _ns_of_head(ctx)
    j = body or {}
    try:
        slashing = ns.AttesterSlashing(
            attestation_1=_json_to_indexed_attestation(ns, j["attestation_1"]),
            attestation_2=_json_to_indexed_attestation(ns, j["attestation_2"]),
        )
    except (KeyError, ValueError, TypeError) as e:
        raise ApiError(400, f"malformed attester slashing: {e!r}") from None
    pool.insert_attester_slashing(slashing)
    if ctx.event_bus is not None:
        ctx.event_bus.publish("attester_slashing", j)
    return {}


def get_pool_bls_changes(ctx, params, query, body):
    ops = _require_op_pool(ctx).contents()["bls_to_execution_changes"]
    return {
        "data": [
            {
                "message": {
                    "validator_index": str(int(c.message.validator_index)),
                    "from_bls_pubkey": hex_(c.message.from_bls_pubkey),
                    "to_execution_address": hex_(
                        c.message.to_execution_address
                    ),
                },
                "signature": hex_(c.signature),
            }
            for c in ops
        ]
    }


def post_pool_bls_changes(ctx, params, query, body):
    pool = _require_op_pool(ctx)
    ns = _ns_of_head(ctx)
    failures = []
    for i, j in enumerate(body or []):
        try:
            change = ns.SignedBLSToExecutionChange(
                message=ns.BLSToExecutionChange(
                    validator_index=int(j["message"]["validator_index"]),
                    from_bls_pubkey=_b(j["message"]["from_bls_pubkey"], 48),
                    to_execution_address=_b(
                        j["message"]["to_execution_address"], 20
                    ),
                ),
                signature=_b(j["signature"], 96),
            )
            pool.insert_bls_to_execution_change(change)
            if ctx.event_bus is not None:
                ctx.event_bus.publish("bls_to_execution_change", j)
        except Exception as e:
            failures.append({"index": i, "message": repr(e)})
    if failures:
        raise ApiError(400, json.dumps(failures))
    return {}


def post_pool_sync_committees(ctx, params, query, body):
    """POST /eth/v1/beacon/pool/sync_committees: SyncCommitteeMessages
    placed at the validator's position(s) in the current committee."""
    if ctx.sync_pool is None:
        raise ApiError(503, "sync committee pool not wired")
    snap = ctx.snapshot()
    state = snap.head_state
    if not hasattr(state, "current_sync_committee"):
        raise ApiError(400, "pre-Altair state has no sync committees")
    cols = accessors.registry_columns(state)
    committee_pks = [bytes(pk) for pk in state.current_sync_committee.pubkeys]
    failures = []
    for i, j in enumerate(body or []):
        try:
            vi = int(j["validator_index"])
            pk = bytes(cols.pubkeys[vi])
            positions = [
                pos for pos, cpk in enumerate(committee_pks) if cpk == pk
            ]
            if not positions:
                raise ValueError(
                    f"validator {vi} not in the current sync committee"
                )
            for pos in positions:
                ctx.sync_pool.insert_message(
                    int(j["slot"]),
                    _b(j["beacon_block_root"], 32),
                    pos,
                    _b(j["signature"], 96),
                )
        except Exception as e:
            failures.append({"index": i, "message": repr(e)})
    if failures:
        raise ApiError(400, json.dumps(failures))
    return {}


# -------------------------------------------------- state breadth handlers
# reference: http_api/src/routing.rs:341-369


def get_state_committees(ctx, params, query, body):
    from grandine_tpu.consensus import misc

    p = ctx.cfg.preset
    state = ctx.resolve_state(params["state_id"])
    epoch = (
        int(query["epoch"])
        if "epoch" in query
        else accessors.get_current_epoch(state, p)
    )
    want_slot = int(query["slot"]) if "slot" in query else None
    want_index = int(query["index"]) if "index" in query else None
    start = misc.compute_start_slot_at_epoch(epoch, p)
    try:
        count = accessors.get_committee_count_per_slot(state, epoch, p)
    except Exception:
        raise ApiError(400, f"epoch {epoch} out of committee range") from None
    rows = []
    for slot in range(start, start + p.SLOTS_PER_EPOCH):
        if want_slot is not None and slot != want_slot:
            continue
        for index in range(count):
            if want_index is not None and index != want_index:
                continue
            committee = accessors.get_beacon_committee(state, slot, index, p)
            rows.append({
                "index": str(index),
                "slot": str(slot),
                "validators": [str(int(v)) for v in committee],
            })
    return {"execution_optimistic": False, "finalized": False, "data": rows}


def _sync_committee_for_epoch(state, epoch: int, p):
    """Current or next sync committee covering `epoch`, or a 400 —
    shared by the sync_committees state route and sync duties."""
    if not hasattr(state, "current_sync_committee"):
        raise ApiError(400, "pre-Altair state has no sync committees")
    cur_epoch = accessors.get_current_epoch(state, p)
    period = p.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
    if epoch // period == cur_epoch // period:
        return state.current_sync_committee
    if epoch // period == cur_epoch // period + 1:
        return state.next_sync_committee
    raise ApiError(400, f"epoch {epoch} outside known sync periods")


def get_state_sync_committees(ctx, params, query, body):
    state = ctx.resolve_state(params["state_id"])
    p = ctx.cfg.preset
    epoch = (
        int(query["epoch"])
        if "epoch" in query
        else accessors.get_current_epoch(state, p)
    )
    committee = _sync_committee_for_epoch(state, epoch, p)
    cols = accessors.registry_columns(state)
    by_pk = {bytes(cols.pubkeys[i]): i for i in range(len(cols))}
    indices = []
    for pk in committee.pubkeys:
        vi = by_pk.get(bytes(pk))
        if vi is None:
            raise ApiError(500, "sync committee pubkey not in registry")
        indices.append(vi)
    from grandine_tpu.p2p.subnets import SYNC_COMMITTEE_SUBNET_COUNT

    agg_size = p.SYNC_COMMITTEE_SIZE // SYNC_COMMITTEE_SUBNET_COUNT
    aggregates = [
        [str(v) for v in indices[i : i + agg_size]]
        for i in range(0, len(indices), agg_size)
    ]
    return {
        "execution_optimistic": False,
        "finalized": False,
        "data": {
            "validators": [str(v) for v in indices],
            "validator_aggregates": aggregates,
        },
    }


def get_state_validator_balances(ctx, params, query, body):
    state = ctx.resolve_state(params["state_id"])
    ids = query.get("id")
    if ids:
        try:
            indices = [int(i) for i in ids.split(",")]
        except ValueError:
            raise ApiError(400, f"invalid id list {ids!r}") from None
    else:
        indices = range(len(state.balances))
    return {
        "execution_optimistic": False,
        "finalized": False,
        "data": [
            {"index": str(i), "balance": str(int(state.balances[i]))}
            for i in indices
            if 0 <= i < len(state.balances)
        ],
    }


def get_state_validator(ctx, params, query, body):
    state = ctx.resolve_state(params["state_id"])
    p = ctx.cfg.preset
    epoch = accessors.get_current_epoch(state, p)
    vid = params["validator_id"]
    if vid.startswith("0x"):
        pk = _b(vid, 48)
        cols = accessors.registry_columns(state)
        matches = [
            i for i in range(len(cols)) if bytes(cols.pubkeys[i]) == pk
        ]
        if not matches:
            raise ApiError(404, "validator not found")
        index = matches[0]
    else:
        index = _parse_int(vid, "validator id")
        if not 0 <= index < len(state.validators):
            raise ApiError(404, "validator not found")
    v = state.validators[index]
    balance = int(state.balances[index])
    return {
        "execution_optimistic": False,
        "finalized": False,
        "data": {
            "index": str(index),
            "balance": str(balance),
            "status": _validator_status(v, balance, epoch),
            "validator": {
                "pubkey": hex_(v.pubkey),
                "withdrawal_credentials": hex_(v.withdrawal_credentials),
                "effective_balance": str(int(v.effective_balance)),
                "slashed": bool(v.slashed),
                "activation_eligibility_epoch": str(
                    int(v.activation_eligibility_epoch)
                ),
                "activation_epoch": str(int(v.activation_epoch)),
                "exit_epoch": str(int(v.exit_epoch)),
                "withdrawable_epoch": str(int(v.withdrawable_epoch)),
            },
        },
    }


def get_header_by_id(ctx, params, query, body):
    node = ctx.resolve_block(params["block_id"])
    snap = ctx.snapshot()
    return {
        "execution_optimistic": False,
        "finalized": False,
        "data": {
            "root": hex_(node.root),
            "canonical": node.root == snap.head_root
            or _is_canonical(ctx, node),
            "header": {
                "message": {
                    "slot": str(node.slot),
                    "parent_root": hex_(node.parent_root),
                    "state_root": hex_(node.state.hash_tree_root()),
                },
            },
        },
    }


def _is_canonical(ctx, node) -> bool:
    store = ctx.controller.store
    cur = store.blocks.get(ctx.snapshot().head_root)
    while cur is not None and cur.slot > node.slot:
        cur = store.blocks.get(cur.parent_root)
    return cur is not None and cur.root == node.root


def get_block_attestations(ctx, params, query, body):
    node = ctx.resolve_block(params["block_id"])
    signed = node.signed_block
    message = getattr(signed, "message", None)
    if message is None:
        raise ApiError(404, "anchor block body unavailable")
    return {
        "execution_optimistic": False,
        "finalized": False,
        "data": [
            _attestation_to_json(a) for a in message.body.attestations
        ],
    }


# --------------------------------------------- block production / publish
# reference: http_api block production v2/v3 + publish (routing.rs:221-287)


def produce_block_v3(ctx, params, query, body):
    from grandine_tpu.validator.duties import produce_block_unsigned

    slot = _parse_int(params["slot"], "slot")
    reveal_hex = query.get("randao_reveal")
    if not reveal_hex:
        raise ApiError(400, "randao_reveal query parameter is required")
    reveal = _b(reveal_hex, 96)
    graffiti = (
        _b(query["graffiti"], 32) if "graffiti" in query else b"\x00" * 32
    )
    snap = ctx.snapshot()
    if slot <= int(snap.head_state.slot):
        raise ApiError(400, f"slot {slot} is not beyond the head")
    state = ctx.controller.state_at_slot(slot, snap)
    attestations = (
        ctx.attestation_pool.pack_attestations(state, ctx.cfg, slot=slot)
        if ctx.attestation_pool is not None
        else []
    )
    ops = (
        ctx.operation_pool.pack(state)
        if ctx.operation_pool is not None
        else {}
    )
    try:
        block, _pre, post = produce_block_unsigned(
            state,
            slot,
            ctx.cfg,
            reveal,
            graffiti=graffiti,
            attestations=attestations,
            full_sync_participation=False,
            voluntary_exits=ops.get("voluntary_exits", ()),
            proposer_slashings=ops.get("proposer_slashings", ()),
            attester_slashings=ops.get("attester_slashings", ()),
            bls_to_execution_changes=ops.get("bls_to_execution_changes", ()),
        )
    except Exception as e:
        raise ApiError(500, f"block production failed: {e!r}")
    version = state_phase_of(post, ctx.cfg).key
    return {
        "version": version,
        "execution_payload_blinded": False,
        "execution_payload_value": "0",
        "consensus_block_value": "0",
        "data": {
            "slot": str(slot),
            "proposer_index": str(int(block.proposer_index)),
            "message_root": hex_(block.hash_tree_root()),
            "ssz": hex_(block.serialize()),
        },
    }


def publish_block(ctx, params, query, body):
    """POST /eth/v{1,2}/beacon/blocks: signed block as {"ssz": "0x…"}
    (the SSZ octet body of the reference, carried in JSON)."""
    from grandine_tpu.types.combined import decode_signed_block

    if not isinstance(body, dict) or "ssz" not in body:
        raise ApiError(400, 'expected {"ssz": "0x…"} body')
    try:
        signed = decode_signed_block(_b(body["ssz"]), ctx.cfg)
    except Exception as e:
        raise ApiError(400, f"malformed block: {e!r}") from None
    ctx.controller.on_gossip_block(signed)
    if ctx.network is not None:
        try:
            ctx.network.publish_block(signed)
        except Exception:
            pass  # local import already queued; gossip is best-effort
    return {}


# ------------------------------------------------- validator breadth
# reference: http_api validator routes (aggregates, sync duties,
# preparation/registration)


def post_aggregate_and_proofs(ctx, params, query, body):
    if ctx.attestation_pool is None:
        raise ApiError(503, "attestation pool not wired")
    ns = _ns_of_head(ctx)
    failures = []
    for i, j in enumerate(body or []):
        try:
            att = _attestation_from_json(ctx, j["message"]["aggregate"])
            ctx.attestation_pool.insert(att)
            if ctx.network is not None:
                # rebroadcast so peers see the aggregate (network.rs
                # publishes API-submitted aggregates to gossip)
                signed = ns.SignedAggregateAndProof(
                    message=ns.AggregateAndProof(
                        aggregator_index=int(j["message"]["aggregator_index"]),
                        aggregate=att,
                        selection_proof=_b(
                            j["message"]["selection_proof"], 96
                        ),
                    ),
                    signature=_b(j["signature"], 96),
                )
                ctx.network.publish_aggregate(signed)
        except Exception as e:
            failures.append({"index": i, "message": repr(e)})
    if failures:
        raise ApiError(400, json.dumps(failures))
    return {}


def get_aggregate_attestation(ctx, params, query, body):
    if ctx.attestation_pool is None:
        raise ApiError(503, "attestation pool not wired")
    slot = _parse_int(query.get("slot"), "slot")
    root = _b(query.get("attestation_data_root", ""), 32)
    att = ctx.attestation_pool.best_by_data_root(slot, root)
    if att is None:
        raise ApiError(404, "no matching aggregate")
    return {"data": _attestation_to_json(att)}


def post_sync_duties(ctx, params, query, body):
    """POST /eth/v1/validator/duties/sync/{epoch} for the posted indices."""
    p = ctx.cfg.preset
    epoch = _parse_int(params["epoch"], "epoch")
    snap = ctx.snapshot()
    state = snap.head_state
    if not hasattr(state, "current_sync_committee"):
        return {"data": []}
    committee = _sync_committee_for_epoch(state, epoch, p)
    want = {_parse_int(i, "validator index") for i in (body or [])}
    cols = accessors.registry_columns(state)
    duties = []
    for vi in sorted(want):
        if not 0 <= vi < len(cols):
            continue
        pk = bytes(cols.pubkeys[vi])
        positions = [
            pos
            for pos, cpk in enumerate(committee.pubkeys)
            if bytes(cpk) == pk
        ]
        if positions:
            duties.append({
                "pubkey": hex_(pk),
                "validator_index": str(vi),
                "validator_sync_committee_indices": [
                    str(p_) for p_ in positions
                ],
            })
    return {"data": duties}


def post_prepare_beacon_proposer(ctx, params, query, body):
    for j in body or []:
        try:
            index = int(j["validator_index"])
            ctx.prepared_proposers[index] = j["fee_recipient"]
        except (KeyError, ValueError, TypeError) as e:
            raise ApiError(400, f"malformed preparation: {e!r}") from None
    return {}


def post_register_validator(ctx, params, query, body):
    for j in body or []:
        try:
            pk = j["message"]["pubkey"]
            ctx.validator_registrations[pk] = j
        except (KeyError, TypeError) as e:
            raise ApiError(400, f"malformed registration: {e!r}") from None
    return {}


def post_beacon_committee_subscriptions(ctx, params, query, body):
    if ctx.subnet_service is None:
        raise ApiError(503, "subnet service not wired")
    for j in body or []:
        try:
            ctx.subnet_service.subscribe_attestation(
                validator_index=int(j["validator_index"]),
                committee_index=int(j["committee_index"]),
                committees_at_slot=int(j["committees_at_slot"]),
                slot=int(j["slot"]),
                is_aggregator=bool(j.get("is_aggregator", False)),
            )
        except (KeyError, ValueError, TypeError) as e:
            raise ApiError(400, f"malformed subscription: {e!r}") from None
    return {}


def post_sync_committee_subscriptions(ctx, params, query, body):
    if ctx.subnet_service is None:
        raise ApiError(503, "subnet service not wired")
    for j in body or []:
        try:
            ctx.subnet_service.subscribe_sync_committee(
                validator_index=int(j["validator_index"]),
                sync_committee_indices=[
                    int(i) for i in j["sync_committee_indices"]
                ],
                until_epoch=int(j["until_epoch"]),
            )
        except (KeyError, ValueError, TypeError) as e:
            raise ApiError(400, f"malformed subscription: {e!r}") from None
    return {}


# ------------------------------------------------------- node breadth


def get_node_identity(ctx, params, query, body):
    net = ctx.network
    transport = getattr(net, "transport", net) if net is not None else None
    return {
        "data": {
            "peer_id": getattr(transport, "peer_id", ""),
            "enr": getattr(transport, "enr", ""),
            "p2p_addresses": list(getattr(transport, "addresses", ()) or ()),
            "discovery_addresses": [],
            "metadata": {"seq_number": "0", "attnets": "0x" + "00" * 8},
        }
    }


def get_node_peers(ctx, params, query, body):
    peers = []
    for p in _net_peers(ctx):
        if not isinstance(p, dict):  # Transport.peers() returns ids
            p = {"peer_id": p}
        peers.append({
            "peer_id": str(p.get("peer_id", "")),
            "last_seen_p2p_address": str(p.get("address", "")),
            "state": p.get("state", "connected"),
            "direction": p.get("direction", "outbound"),
        })
    return {"data": peers, "meta": {"count": len(peers)}}


def _net_peers(ctx) -> list:
    net = ctx.network
    if net is None:
        return []
    # a Network wraps its Transport; either may be handed in
    transport = getattr(net, "transport", net)
    try:
        return list(transport.peers())
    except Exception:
        return []


def get_node_peer_count(ctx, params, query, body):
    connected = len(_net_peers(ctx))
    return {
        "data": {
            "disconnected": "0",
            "connecting": "0",
            "connected": str(connected),
            "disconnecting": "0",
        }
    }


# ----------------------------------------------- keymanager API handlers
# reference: the keymanager crate's routes served by http_api
# (keymanager-API spec: keystores / remotekeys / per-validator
# feerecipient, gas_limit, graffiti)


def _require_km(ctx):
    if ctx.keymanager is None:
        raise ApiError(503, "keymanager not wired")
    return ctx.keymanager


def _pubkey_param(params) -> bytes:
    raw = params["pubkey"]
    try:
        pk = bytes.fromhex(raw.removeprefix("0x"))
    except ValueError:
        raise ApiError(400, f"invalid pubkey {raw!r}") from None
    if len(pk) != 48:
        raise ApiError(400, "pubkey must be 48 bytes")
    return pk


def get_keystores(ctx, params, query, body):
    return {"data": _require_km(ctx).list_keystores()}


def post_keystores(ctx, params, query, body):
    km = _require_km(ctx)
    body = body or {}
    keystores = [
        json.loads(k) if isinstance(k, str) else k
        for k in body.get("keystores", [])
    ]
    passwords = body.get("passwords", [])
    if len(keystores) != len(passwords):
        raise ApiError(400, "keystores/passwords length mismatch")
    interchange = body.get("slashing_protection")
    if interchange and km.slashing_protection is not None:
        km.slashing_protection.import_interchange(
            json.loads(interchange)
            if isinstance(interchange, str)
            else interchange
        )
    return {"data": km.import_keystores(keystores, passwords)}


def delete_keystores(ctx, params, query, body):
    km = _require_km(ctx)
    try:
        pubkeys = [_b(p, 48) for p in (body or {}).get("pubkeys", [])]
    except ValueError:
        raise ApiError(400, "malformed pubkey in delete request") from None
    statuses = km.delete_keystores(pubkeys)
    protection = (
        json.dumps(km.slashing_protection.export_interchange())
        if km.slashing_protection is not None
        else json.dumps({"metadata": {}, "data": []})
    )
    return {"data": statuses, "slashing_protection": protection}


def get_remote_keys(ctx, params, query, body):
    return {"data": _require_km(ctx).list_remote_keys()}


def post_remote_keys(ctx, params, query, body):
    km = _require_km(ctx)
    return {"data": km.import_remote_keys((body or {}).get("remote_keys", []))}


def delete_remote_keys(ctx, params, query, body):
    km = _require_km(ctx)
    try:
        pubkeys = [_b(p, 48) for p in (body or {}).get("pubkeys", [])]
    except ValueError:
        raise ApiError(400, "malformed pubkey in delete request") from None
    return {"data": km.delete_remote_keys(pubkeys)}


def get_fee_recipient(ctx, params, query, body):
    km = _require_km(ctx)
    pk = _pubkey_param(params)
    addr = km.proposer_config(pk).get("fee_recipient")
    if addr is None:
        raise ApiError(404, "no fee recipient configured")
    return {"data": {"pubkey": hex_(pk), "ethaddress": hex_(addr)}}


def post_fee_recipient(ctx, params, query, body):
    km = _require_km(ctx)
    pk = _pubkey_param(params)
    try:
        addr = _b((body or {}).get("ethaddress", ""), 20)
    except ValueError:
        raise ApiError(400, "malformed ethaddress") from None
    km.set_fee_recipient(pk, addr)
    return {}


def delete_fee_recipient(ctx, params, query, body):
    km = _require_km(ctx)
    if not km.delete_proposer_field(_pubkey_param(params), "fee_recipient"):
        raise ApiError(404, "no fee recipient configured")
    return {}


def get_gas_limit(ctx, params, query, body):
    km = _require_km(ctx)
    pk = _pubkey_param(params)
    limit = km.proposer_config(pk).get("gas_limit")
    if limit is None:
        raise ApiError(404, "no gas limit configured")
    return {"data": {"pubkey": hex_(pk), "gas_limit": str(limit)}}


def post_gas_limit(ctx, params, query, body):
    km = _require_km(ctx)
    pk = _pubkey_param(params)
    km.set_gas_limit(pk, _parse_int((body or {}).get("gas_limit"), "gas_limit"))
    return {}


def delete_gas_limit(ctx, params, query, body):
    km = _require_km(ctx)
    if not km.delete_proposer_field(_pubkey_param(params), "gas_limit"):
        raise ApiError(404, "no gas limit configured")
    return {}


def get_graffiti(ctx, params, query, body):
    km = _require_km(ctx)
    pk = _pubkey_param(params)
    graffiti = km.proposer_config(pk).get("graffiti")
    if graffiti is None:
        raise ApiError(404, "no graffiti configured")
    return {
        "data": {
            "pubkey": hex_(pk),
            "graffiti": graffiti.decode("utf-8", "replace").rstrip("\x00"),
        }
    }


def post_graffiti(ctx, params, query, body):
    km = _require_km(ctx)
    pk = _pubkey_param(params)
    text = (body or {}).get("graffiti", "")
    raw = text.encode()[:32].ljust(32, b"\x00")
    km.set_graffiti(pk, raw)
    return {}


def delete_graffiti(ctx, params, query, body):
    km = _require_km(ctx)
    if not km.delete_proposer_field(_pubkey_param(params), "graffiti"):
        raise ApiError(404, "no graffiti configured")
    return {}


def build_router() -> Router:
    r = Router()
    r.add("GET", "/eth/v1/node/version", get_node_version)
    r.add("GET", "/eth/v1/node/health", get_node_health)
    r.add("GET", "/eth/v1/node/syncing", get_node_syncing)
    r.add("GET", "/eth/v1/debug/fork_choice", get_debug_fork_choice)
    r.add("GET", "/eth/v2/debug/beacon/heads", get_debug_heads)
    r.add("GET", "/eth/v2/debug/beacon/states/{state_id}", get_debug_state)
    r.add("GET", "/eth/v1/beacon/genesis", get_genesis)
    r.add("GET", "/eth/v1/beacon/states/{state_id}/root", get_state_root)
    r.add("GET", "/eth/v1/beacon/states/{state_id}/fork", get_state_fork)
    r.add(
        "GET",
        "/eth/v1/beacon/states/{state_id}/finality_checkpoints",
        get_finality_checkpoints,
    )
    r.add(
        "GET", "/eth/v1/beacon/states/{state_id}/validators", get_state_validators
    )
    r.add("GET", "/eth/v1/beacon/headers", get_headers)
    r.add("GET", "/eth/v2/beacon/blocks/{block_id}", get_block)
    r.add("GET", "/eth/v1/beacon/blocks/{block_id}/root", get_block_root)
    r.add("POST", "/eth/v1/beacon/pool/attestations", post_pool_attestations)
    r.add("GET", "/eth/v1/beacon/pool/voluntary_exits", get_pool_voluntary_exits)
    r.add("GET", "/eth/v1/config/spec", get_config_spec)
    r.add("GET", "/eth/v1/config/deposit_contract", get_deposit_contract)
    r.add("POST", "/eth/v1/validator/liveness/{epoch}", post_validator_liveness)
    r.add("GET", "/eth/v1/validator/duties/proposer/{epoch}", get_proposer_duties)
    r.add("POST", "/eth/v1/validator/duties/attester/{epoch}", post_attester_duties)
    r.add("GET", "/metrics", get_metrics)
    r.add("GET", "/eth/v1/debug/grandine/trace", get_debug_trace)
    r.add("GET", "/eth/v1/debug/grandine/flight", get_debug_flight)
    r.add("GET", "/eth/v1/debug/grandine/profile", get_debug_profile)
    # state breadth (routing.rs:341-369)
    r.add(
        "GET", "/eth/v1/beacon/states/{state_id}/committees",
        get_state_committees,
    )
    r.add(
        "GET", "/eth/v1/beacon/states/{state_id}/sync_committees",
        get_state_sync_committees,
    )
    r.add(
        "GET", "/eth/v1/beacon/states/{state_id}/validator_balances",
        get_state_validator_balances,
    )
    r.add(
        "GET",
        "/eth/v1/beacon/states/{state_id}/validators/{validator_id}",
        get_state_validator,
    )
    r.add("GET", "/eth/v1/beacon/headers/{block_id}", get_header_by_id)
    r.add(
        "GET", "/eth/v1/beacon/blocks/{block_id}/attestations",
        get_block_attestations,
    )
    # pool breadth (routing.rs:389-410)
    r.add("GET", "/eth/v1/beacon/pool/attestations", get_pool_attestations)
    r.add(
        "POST", "/eth/v1/beacon/pool/voluntary_exits",
        post_pool_voluntary_exits,
    )
    r.add(
        "GET", "/eth/v1/beacon/pool/proposer_slashings",
        get_pool_proposer_slashings,
    )
    r.add(
        "POST", "/eth/v1/beacon/pool/proposer_slashings",
        post_pool_proposer_slashings,
    )
    r.add(
        "GET", "/eth/v1/beacon/pool/attester_slashings",
        get_pool_attester_slashings,
    )
    r.add(
        "POST", "/eth/v1/beacon/pool/attester_slashings",
        post_pool_attester_slashings,
    )
    r.add(
        "GET", "/eth/v1/beacon/pool/bls_to_execution_changes",
        get_pool_bls_changes,
    )
    r.add(
        "POST", "/eth/v1/beacon/pool/bls_to_execution_changes",
        post_pool_bls_changes,
    )
    r.add(
        "POST", "/eth/v1/beacon/pool/sync_committees",
        post_pool_sync_committees,
    )
    # block production + publish
    r.add("GET", "/eth/v2/validator/blocks/{slot}", produce_block_v3)
    r.add("GET", "/eth/v3/validator/blocks/{slot}", produce_block_v3)
    r.add("POST", "/eth/v1/beacon/blocks", publish_block)
    r.add("POST", "/eth/v2/beacon/blocks", publish_block)
    # validator breadth
    r.add(
        "POST", "/eth/v1/validator/aggregate_and_proofs",
        post_aggregate_and_proofs,
    )
    r.add(
        "GET", "/eth/v1/validator/aggregate_attestation",
        get_aggregate_attestation,
    )
    r.add("POST", "/eth/v1/validator/duties/sync/{epoch}", post_sync_duties)
    r.add(
        "POST", "/eth/v1/validator/prepare_beacon_proposer",
        post_prepare_beacon_proposer,
    )
    r.add(
        "POST", "/eth/v1/validator/register_validator",
        post_register_validator,
    )
    r.add(
        "POST", "/eth/v1/validator/beacon_committee_subscriptions",
        post_beacon_committee_subscriptions,
    )
    r.add(
        "POST", "/eth/v1/validator/sync_committee_subscriptions",
        post_sync_committee_subscriptions,
    )
    # node breadth
    r.add("GET", "/eth/v1/node/identity", get_node_identity)
    r.add("GET", "/eth/v1/node/peers", get_node_peers)
    r.add("GET", "/eth/v1/node/peer_count", get_node_peer_count)
    # keymanager API (served on the same router; the reference runs the
    # keymanager crate's routes under http_api with token auth)
    r.add("GET", "/eth/v1/keystores", get_keystores)
    r.add("POST", "/eth/v1/keystores", post_keystores)
    r.add("DELETE", "/eth/v1/keystores", delete_keystores)
    r.add("GET", "/eth/v1/remotekeys", get_remote_keys)
    r.add("POST", "/eth/v1/remotekeys", post_remote_keys)
    r.add("DELETE", "/eth/v1/remotekeys", delete_remote_keys)
    r.add("GET", "/eth/v1/validator/{pubkey}/feerecipient", get_fee_recipient)
    r.add("POST", "/eth/v1/validator/{pubkey}/feerecipient", post_fee_recipient)
    r.add(
        "DELETE", "/eth/v1/validator/{pubkey}/feerecipient",
        delete_fee_recipient,
    )
    r.add("GET", "/eth/v1/validator/{pubkey}/gas_limit", get_gas_limit)
    r.add("POST", "/eth/v1/validator/{pubkey}/gas_limit", post_gas_limit)
    r.add("DELETE", "/eth/v1/validator/{pubkey}/gas_limit", delete_gas_limit)
    r.add("GET", "/eth/v1/validator/{pubkey}/graffiti", get_graffiti)
    r.add("POST", "/eth/v1/validator/{pubkey}/graffiti", post_graffiti)
    r.add("DELETE", "/eth/v1/validator/{pubkey}/graffiti", delete_graffiti)
    return r


__all__ = ["ApiContext", "ApiError", "Router", "build_router"]
