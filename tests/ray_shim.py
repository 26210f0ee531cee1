"""Minimal ray API shim that executes ``horovod_tpu.ray.RayExecutor``'s
REAL actor path — placement-group request, per-rank actor creation,
coordinator-address announcement from rank 0's actor, env-contract
setup, ``jax.distributed`` world formation, remote execution, shutdown
— with local OS processes standing in for Ray actors.

ray is not installable in this image; like ``mxnet_shim`` and
``pyspark_shim``, this is a test fixture implementing just the surface
the integration touches: ``ray.remote`` class decorator with
``.options(...).remote()``, method ``.remote()`` futures, ``ray.get``
(single/list, timeout), ``ray.kill``, ``ray.util.get_node_ip_address``,
and ``ray.util.placement_group``.  Actor classes and method payloads are
cloudpickled over length-prefixed socketpair frames — a real process
boundary, like Ray's own transport (stdout is left to jax/Gloo
diagnostics; frames get their own fd).
"""

from __future__ import annotations

import os
import pickle
import socket
import struct
import subprocess
import sys
import types
from typing import Any, List


def _write_frame(sock: socket.socket, obj) -> None:
    import cloudpickle

    data = cloudpickle.dumps(obj)
    sock.sendall(struct.pack("<I", len(data)) + data)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise EOFError("actor process died")
        buf += chunk
    return buf


def _read_frame(sock: socket.socket):
    (n,) = struct.unpack("<I", _recv_exact(sock, 4))
    return pickle.loads(_recv_exact(sock, n))


class _Future:
    """One in-flight method call; resolution reads the actor's next
    response frame (calls are FIFO per actor, matching the executor's
    one-outstanding-call usage)."""

    def __init__(self, actor: "_ActorHandle") -> None:
        self._actor = actor

    def _result(self):
        kind, payload = _read_frame(self._actor._sock)
        if kind == "err":
            raise RuntimeError(f"actor raised: {payload}")
        return payload


class _MethodProxy:
    def __init__(self, actor: "_ActorHandle", name: str) -> None:
        self._actor = actor
        self._name = name

    def remote(self, *args, **kwargs) -> _Future:
        _write_frame(self._actor._sock, ("call", self._name, args, kwargs))
        return _Future(self._actor)


class _ActorHandle:
    def __init__(self, cls) -> None:
        env = dict(os.environ)
        tests_dir = os.path.dirname(os.path.abspath(__file__))
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(tests_dir), tests_dir,
             env.get("PYTHONPATH", "")])
        # RPC rides a dedicated socketpair — NOT stdout, which jax/Gloo
        # write diagnostics to.
        parent_sock, child_sock = socket.socketpair()
        env["RAY_SHIM_FD"] = str(child_sock.fileno())
        self._proc = subprocess.Popen(
            [sys.executable, "-c", "import ray_shim; ray_shim._actor_main()"],
            env=env, pass_fds=(child_sock.fileno(),))
        child_sock.close()
        self._sock = parent_sock
        _write_frame(self._sock, ("init", cls))

    def __getattr__(self, name: str) -> _MethodProxy:
        return _MethodProxy(self, name)

    def _kill(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass
        if self._proc.poll() is None:
            self._proc.kill()


class _RemoteClass:
    def __init__(self, cls) -> None:
        self._cls = cls

    def options(self, **_ignored) -> "_RemoteClass":
        return self

    def remote(self, *args, **kwargs) -> _ActorHandle:
        assert not args and not kwargs, "shim actors take no ctor args"
        return _ActorHandle(self._cls)


def _actor_main() -> None:
    """Actor-process entry: instantiate the shipped class, serve calls."""
    os.environ["XLA_FLAGS"] = ""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    install()   # actor methods import ray themselves
    sock = socket.socket(fileno=int(os.environ["RAY_SHIM_FD"]))
    kind, cls = _read_frame(sock)
    assert kind == "init"
    instance = cls()
    while True:
        try:
            kind, name, args, kwargs = _read_frame(sock)
        except EOFError:
            return
        try:
            result = getattr(instance, name)(*args, **kwargs)
            _write_frame(sock, ("ok", result))
        except Exception as e:  # ship the error, keep serving
            _write_frame(sock, ("err", f"{type(e).__name__}: {e}"))


# --- module-level ray API -----------------------------------------------------

def remote(*args, **kwargs):
    if args and isinstance(args[0], type):   # bare @ray.remote
        return _RemoteClass(args[0])

    def deco(cls):
        return _RemoteClass(cls)

    return deco


def get(x, timeout: float = None) -> Any:
    if isinstance(x, list):
        return [get(f, timeout) for f in x]
    if isinstance(x, _Future):
        return x._result()
    return x   # e.g. the placement group's trivial ready() token


def kill(actor: _ActorHandle) -> None:
    actor._kill()


class _PlacementGroup:
    def __init__(self, bundles: List[dict], strategy: str) -> None:
        self.bundles = bundles
        self.strategy = strategy

    def ready(self):
        return "ready"


def _placement_group(bundles, strategy="PACK") -> _PlacementGroup:
    return _PlacementGroup(list(bundles), strategy)


def _remove_placement_group(pg) -> None:
    pass


def install() -> types.ModuleType:
    mod = types.ModuleType("ray")
    mod.remote = remote
    mod.get = get
    mod.kill = kill
    util = types.ModuleType("ray.util")
    util.get_node_ip_address = lambda: "127.0.0.1"
    pg_mod = types.ModuleType("ray.util.placement_group")
    pg_mod.placement_group = _placement_group
    pg_mod.remove_placement_group = _remove_placement_group
    util.placement_group = pg_mod
    mod.util = util
    sys.modules["ray"] = mod
    sys.modules["ray.util"] = util
    sys.modules["ray.util.placement_group"] = pg_mod
    return mod


def uninstall() -> None:
    for m in ("ray", "ray.util", "ray.util.placement_group"):
        sys.modules.pop(m, None)
