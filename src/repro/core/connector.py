"""The Connector interface (paper Fig. 2), re-grounded for accelerator sites.

The paper's connectors shell out to container orchestrators; ours manage
device-mesh *sites*.  The contract is kept method-for-method:

  deploy() / undeploy()                — model (site) lifecycle, called only
                                         by the DeploymentManager (R1)
  get_available_resources(service)     — replicas of a service in this model
  run(resource, command, ...)          — execute a step invocation
  copy(src, dst, kind, source_remote)  — move tokens between the management
                                         node and resources (R3)

Each resource owns an object store (the container filesystem analogue).
``copy`` moves *serialized* payloads so a two-step inter-site transfer has
real, measurable cost (bytes appear in the DataManager transfer log).
A token of device arrays stays on the device in the store it was put in
(a *resident* entry) and becomes bytes only when something asks for bytes.
"""
from __future__ import annotations

import abc
import enum
import hashlib
import pickle
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.core import tracing


class ConnectorCopyKind(enum.Enum):
    LOCAL_TO_REMOTE = "localToRemote"
    REMOTE_TO_LOCAL = "remoteToLocal"
    REMOTE_TO_REMOTE = "remoteToRemote"


@dataclass
class ResourceInfo:
    name: str
    service: str
    cores: int = 1
    memory_gb: float = 4.0


def content_digest(payload: bytes) -> str:
    """Canonical content digest of a serialized payload (sha256 hex).

    This is the identity the whole data plane keys on: CAS dedup inside a
    store, the planner's "already-present" elision across stores, and the
    invocation memo key all hash the same way."""
    return hashlib.sha256(payload).hexdigest()


#: leaves that may sit beside device arrays in a resident value: immutable
#: scalars, which no consumer can change under another
_SCALARS = (bool, int, float, complex, str, bytes, np.number, np.bool_)


def _resident_leaves(value: Any) -> Optional[list]:
    """The pytree leaves of a value that a store keeps as it is: device
    arrays (``jax.Array``, at least one) and immutable scalars.  None for
    any other value, which is stored as bytes."""
    try:
        leaves = jax.tree.leaves(value)
    except ValueError:          # a dict whose keys do not sort
        return None
    if not any(isinstance(x, jax.Array) for x in leaves):
        return None
    if not all(isinstance(x, (jax.Array, *_SCALARS)) for x in leaves):
        return None
    return leaves


def _fresh(x: Any) -> Any:
    """New containers around the same leaves, each in its own order (a
    dict keeps its keys' order, so the copy pickles as the original)."""
    t = type(x)
    if t is dict:
        return {k: _fresh(v) for k, v in x.items()}
    if t is list:
        return [_fresh(v) for v in x]
    if t is tuple:
        return tuple(_fresh(v) for v in x)
    if x is None or isinstance(x, (jax.Array, *_SCALARS)):
        return x
    # any other pytree node (a named tuple, a registered class): one
    # level of its own flatten gives the children its unflatten takes
    children, node = jax.tree.flatten(x, is_leaf=lambda y: y is not x)
    return jax.tree.unflatten(node, [_fresh(c) for c in children])


class _Resident:
    """A value held as it is: a copy of its containers made when it was
    put, its leaves, their byte size, and the digest of its bytes once
    something has asked for them."""
    __slots__ = ("tree", "leaves", "nbytes", "digest", "lock")

    def __init__(self, value: Any, leaves: list):
        self.tree = _fresh(value)
        self.leaves = leaves
        self.nbytes = sum(getattr(x, "nbytes", 0) for x in leaves)
        self.digest: Optional[str] = None
        self.lock = threading.Lock()      # one materialization at a time

    def value(self) -> Any:
        """Fresh containers around the same immutable leaves."""
        return _fresh(self.tree)

    def dead(self) -> bool:
        return any(isinstance(x, jax.Array) and x.is_deleted()
                   for x in self.leaves)


class ObjectStore:
    """Per-resource content-addressed payload store with byte accounting.

    Paths (token keys) index into a digest-keyed CAS: each distinct payload
    is held once, however many paths reference it, so duplicate puts on a
    site cost no extra memory and ``size``/``exists`` answer from the
    index alone.  ``name`` identifies the owning resource (or site, for
    shared stores) so a missed lookup names where the token was expected,
    not just its key.

    Values go in and out through ``put_value``/``get_value``.  A value of
    device arrays (:func:`_resident_leaves`) is held as it is, a *resident*
    entry: a consumer on the same store gets fresh containers around the
    same arrays, with no pickle, hash or host copy.  Its bytes are made
    once, on the first call that needs them (``get``, ``digest_of``,
    ``demote``), with the same ``serialize`` and hash as a bytes put;
    ``release`` lets go of the arrays without making any.  Any other
    value is serialized and put.

    Byte accounting is deliberately *logical*: ``bytes_in``/``bytes_out``
    count what callers pushed/pulled (every put and get, dedup or not;
    ``nbytes`` for a resident value) so transfer metrics are invariant to
    the CAS internals; the dedup win is visible separately via
    ``dedup_puts``/``dedup_bytes``/``unique_bytes``, and the resident path
    via ``resident_puts``/``resident_bytes``/``materialized``.  Metadata
    probes (``exists``/``size``/``digest_of``/``has_digest``/
    ``link_digest``) never touch the byte counters."""

    def __init__(self, name: str = "store"):
        self.name = name
        self._cas: Dict[str, bytes] = {}      # digest -> payload (once)
        self._index: Dict[str, str] = {}      # path -> digest
        self._refs: Dict[str, int] = {}       # digest -> live path count
        self._resident: Dict[str, _Resident] = {}  # path -> held value
        self._lock = threading.Lock()
        self.bytes_in = 0
        self.bytes_out = 0
        self.dedup_puts = 0     # puts whose payload was already held
        self.dedup_bytes = 0    # bytes those puts did NOT duplicate
        self.resident_puts = 0  # values held as they are
        self.resident_bytes = 0  # their nbytes
        self.materialized = 0   # resident entries later made into bytes

    # -- internal (lock held) -------------------------------------------------
    def _bind(self, path: str, digest: str):
        old = self._index.get(path)
        if old == digest:
            return
        self._index[path] = digest
        self._refs[digest] = self._refs.get(digest, 0) + 1
        if old is not None:
            self._release(old)

    def _release(self, digest: str):
        n = self._refs.get(digest, 0) - 1
        if n <= 0:
            self._refs.pop(digest, None)
            self._cas.pop(digest, None)
        else:
            self._refs[digest] = n

    def _unbind(self, path: str):
        self._resident.pop(path, None)
        digest = self._index.pop(path, None)
        if digest is not None:
            self._release(digest)

    def _dead_error(self, path: str) -> RuntimeError:
        return RuntimeError(
            f"object store {self.name!r} holds token {path!r} on the "
            f"device, but its buffers were deleted (a consumer donated "
            f"them)")

    # -- data plane -----------------------------------------------------------
    def put(self, path: str, payload: bytes) -> str:
        """Store a payload under ``path``; returns its content digest.
        A duplicate put (payload already in the CAS) only adds an index
        entry — the bytes are not held twice."""
        return self._put(path, payload)

    def _put(self, path: str, payload: bytes,
             held: Optional[_Resident] = None) -> str:
        """Hash and index a payload: a caller's put, or (``held``) the
        bytes of a resident entry, which stays held beside them."""
        with tracing.span("sf:cas_put") as sp:
            digest = content_digest(payload)
            with self._lock:
                if held is not None and self._resident.get(path) is not held:
                    return digest       # replaced or deleted meanwhile
                dedup = digest in self._cas
                if dedup:
                    self.dedup_puts += 1
                    self.dedup_bytes += len(payload)
                else:
                    self._cas[digest] = payload
                self._bind(path, digest)
                if held is None:
                    self._resident.pop(path, None)
                    self.bytes_in += len(payload)
                else:
                    held.digest = digest
            sp.set("bytes", len(payload))
            sp.set("dedup", dedup)
        return digest

    def put_value(self, path: str, value: Any):
        """Store a value under ``path``: held as it is when it is device
        arrays (once they are ready, so that device errors surface in the
        producer), else serialized and put."""
        leaves = _resident_leaves(value)
        if leaves is None:
            self.put(path, serialize(value))
            return
        held = _Resident(value, leaves)
        with tracing.span("sf:hold") as sp:
            jax.block_until_ready(held.leaves)
            sp.set("bytes", held.nbytes)
            sp.set("key", path)
        with self._lock:
            self._unbind(path)
            self._resident[path] = held
            self.bytes_in += held.nbytes
            self.resident_puts += 1
            self.resident_bytes += held.nbytes

    def get_value(self, path: str) -> Any:
        """The value stored at ``path``: a resident entry's arrays in
        fresh containers, else the payload deserialized."""
        with self._lock:
            held = self._resident.get(path)
            if held is not None:
                if held.dead():
                    raise self._dead_error(path)
                self.bytes_out += held.nbytes
        if held is None:
            return deserialize(self.get(path))
        return held.value()

    def _materialize(self, path: str):
        """Make a resident entry's bytes, once; nothing for other paths."""
        with self._lock:
            held = self._resident.get(path)
        if held is None:
            return
        with held.lock:
            if held.digest is not None:
                return
            if held.dead():
                raise self._dead_error(path)
            payload = serialize(held.tree, lazy=True)
            with self._lock:
                self.materialized += 1
            self._put(path, payload, held)

    def get(self, path: str) -> bytes:
        self._materialize(path)
        with self._lock:
            digest = self._index.get(path)
            payload = self._cas.get(digest) if digest is not None else None
            if payload is None:
                raise KeyError(
                    f"object store {self.name!r} holds no payload at "
                    f"{path!r} — the token was never transferred here, or "
                    f"the site was redeployed and lost it")
            self.bytes_out += len(payload)
            return payload

    def exists(self, path: str) -> bool:
        with self._lock:
            return path in self._index or path in self._resident

    def size(self, path: str) -> int:
        """Byte length of a stored payload, or -1 when absent.  A resident
        entry answers its arrays' ``nbytes`` until its bytes are made, and
        their length after, as a bytes entry does.  A metadata probe: does
        NOT touch the bytes_in/bytes_out accounting, so planners may ask
        freely without polluting transfer metrics."""
        with self._lock:
            digest = self._index.get(path)
            if digest is None:
                held = self._resident.get(path)
                return -1 if held is None else held.nbytes
            return len(self._cas[digest])

    def delete(self, path: str):
        """Drop a path; the payload survives while other paths share its
        digest and is freed with the last reference."""
        with self._lock:
            self._unbind(path)

    def paths(self) -> List[str]:
        with self._lock:
            return list(dict.fromkeys([*self._index, *self._resident]))

    def release(self, prefix: str) -> int:
        """Let go of the device arrays of the resident entries under
        ``prefix``, making no bytes: an entry whose bytes were made keeps
        them, one without is dropped.  Returns the number dropped."""
        with self._lock:
            paths = [p for p in self._resident if p.startswith(prefix)]
            for path in paths:
                del self._resident[path]
            return sum(p not in self._index for p in paths)

    def demote(self) -> int:
        """Turn every resident entry into bytes and let go of its device
        arrays, so that it stays readable by path; entries whose buffers
        were donated are dropped.  Returns the number of entries demoted."""
        with self._lock:
            paths = list(self._resident)
        done = 0
        for path in paths:
            with self._lock:
                held = self._resident.get(path)
                if held is None:
                    continue
                if held.dead():
                    del self._resident[path]
                    continue
            self._materialize(path)
            with self._lock:
                if self._resident.get(path) is held:
                    del self._resident[path]
                    done += 1
        return done

    def clear(self):
        """Drop every path and payload (the site is undeployed)."""
        with self._lock:
            self._cas.clear()
            self._index.clear()
            self._refs.clear()
            self._resident.clear()

    # -- content addressing (all metadata probes: counter-neutral) ------------
    def digest_of(self, path: str) -> Optional[str]:
        """Content digest stored at ``path``, or None when absent.  A
        resident entry is made into bytes for it."""
        self._materialize(path)
        with self._lock:
            return self._index.get(path)

    def has_digest(self, digest: str) -> bool:
        """True if any live path in this store holds the payload."""
        with self._lock:
            return digest in self._cas

    def link_digest(self, path: str, digest: str) -> bool:
        """Alias ``path`` to a payload already in the CAS — the zero-cost
        'already-present' route.  Returns False (and changes nothing) when
        the digest is not held here; no bytes move either way."""
        with self._lock:
            if digest not in self._cas:
                return False
            self._resident.pop(path, None)
            self._bind(path, digest)
            return True

    def unique_bytes(self) -> int:
        """Payload bytes physically held (one copy per digest)."""
        with self._lock:
            return sum(len(p) for p in self._cas.values())


def serialize(value: Any, *, lazy: bool = False) -> bytes:
    """Pickle a value; ``lazy`` marks the bytes of a resident entry, made
    when they were first asked for."""
    with tracing.span("sf:encode") as sp:
        payload = pickle.dumps(value, protocol=pickle.HIGHEST_PROTOCOL)
        sp.set("bytes", len(payload))
        if lazy:
            sp.set("lazy", True)
    return payload


def deserialize(payload: bytes) -> Any:
    with tracing.span("sf:decode") as sp:
        sp.set("bytes", len(payload))
        return pickle.loads(payload)


class Connector(abc.ABC):
    """One *model* (deployment unit).  Subclasses define the site semantics.

    Mirrors the paper's design: a new Connector façade can be handed out per
    caller (``clone``) while the underlying site state is shared — avoiding
    cross-thread conflicts without fully-atomic method access.
    """

    def __init__(self, name: str, config: Optional[dict] = None):
        self.name = name
        self.config = config or {}
        self.deployed = False
        self._alive = True

    # -- lifecycle (R1: atomic unit; only DeploymentManager calls these) ----
    @abc.abstractmethod
    def deploy(self) -> None:
        ...

    @abc.abstractmethod
    def undeploy(self) -> None:
        ...

    # -- discovery -----------------------------------------------------------
    @abc.abstractmethod
    def get_available_resources(self, service: str) -> List[str]:
        ...

    @abc.abstractmethod
    def resource_info(self, resource: str) -> ResourceInfo:
        ...

    # -- execution ------------------------------------------------------------
    @abc.abstractmethod
    def run(self, resource: str, command: Any,
            environment: Optional[Dict[str, str]] = None,
            workdir: Optional[str] = None,
            capture_output: bool = False) -> Any:
        ...

    # -- data plane -----------------------------------------------------------
    @abc.abstractmethod
    def store(self, resource: str) -> ObjectStore:
        ...

    @abc.abstractmethod
    def stores(self) -> List[ObjectStore]:
        """The site's distinct stores (one, where they are shared)."""

    def copy(self, src: str, dst: str, kind: ConnectorCopyKind,
             source_remote: Optional[str] = None, *,
             local_store: Optional[ObjectStore] = None,
             dest_remote: Optional[str] = None,
             peer: Optional["Connector"] = None,
             link=None) -> int:
        """Move one payload; returns bytes moved.

        src/dst are store paths (token keys).  ``source_remote`` /
        ``dest_remote`` name resources for the remote ends;
        ``local_store`` is the management node's store.

        Config may declare a simulated WAN link between this site and the
        management node (``link_latency_s`` per copy + ``link_bandwidth_mbps``)
        so cross-site hops have real, measurable cost — this is what the
        executor overlaps with compute.

        ``REMOTE_TO_REMOTE`` with a ``peer`` connector is the *direct*
        cross-model channel (topology-routed transfers): the payload moves
        from this site's store straight into the peer site's store, paying
        the declared ``link`` cost (a ``topology.LinkSpec``) and never
        touching the management node.  Without a peer it is the classic
        intra-model hop.
        """
        if kind is ConnectorCopyKind.LOCAL_TO_REMOTE:
            payload = local_store.get(src)
            self._link_delay(len(payload))
            self.store(dest_remote).put(dst, payload)
        elif kind is ConnectorCopyKind.REMOTE_TO_LOCAL:
            payload = self.store(source_remote).get(src)
            self._link_delay(len(payload))
            local_store.put(dst, payload)
        elif peer is not None and peer.name != self.name:
            # direct site-to-site hop over a declared topology link
            payload = self.store(source_remote).get(src)
            if link is not None:
                delay = link.cost(len(payload))
                if delay > 0:
                    time.sleep(delay)
            peer.store(dest_remote).put(dst, payload)
        else:  # REMOTE_TO_REMOTE within this model
            payload = self.store(source_remote).get(src)
            self.store(dest_remote).put(dst, payload)
        return len(payload)

    def _link_delay(self, n_bytes: int):
        """Sleep out the declared management-node link cost (0 by default)."""
        latency = float(self.config.get("link_latency_s", 0.0))
        mbps = float(self.config.get("link_bandwidth_mbps", 0.0))
        delay = latency + (n_bytes * 8 / (mbps * 1e6) if mbps > 0 else 0.0)
        if delay > 0:
            time.sleep(delay)

    def services(self) -> List[str]:
        """Service names this model exposes (wrappers may delegate)."""
        return list(self.config.get("services", {"default": {}}).keys())

    # -- hybrid-data-space hints (R3/R4 optimisations) ------------------------
    def shared_data_space(self) -> bool:
        """True if all resources in this model see one store (e.g. the
        paper's Occam /scratch LUSTRE mount)."""
        return False

    # -- health (fault-tolerance hooks) ---------------------------------------
    def ping(self, resource: Optional[str] = None) -> bool:
        return self._alive and self.deployed

    def clone(self) -> "Connector":
        """Per-caller façade sharing the underlying site state (paper §4.5)."""
        import copy as _copy
        twin = _copy.copy(self)
        return twin
