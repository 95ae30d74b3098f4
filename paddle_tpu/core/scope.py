"""Scope: name -> value store for persistable state.

Reference analogue: framework::Scope (scope.h:46) holding type-erased
Variables. Here a Scope maps var names to device arrays (jax.Array) or host
numpy arrays; the Executor donates the persistable sub-dict into each jitted
step so parameter updates are in-place at the XLA buffer level — the
functional-JAX answer to the reference's mutable-Scope optimizer kernels.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional

import numpy as np



# Scope pool (reference: framework/scope_pool.{h,cc} — tracks every
# Python-created Scope so leaked ones can be cleared deterministically,
# the notebook/REPL hygiene hook exposed as core._ScopePool in pybind).
# Entries are weak: a Scope dies normally with its last reference; the
# pool only lets you bulk-release the arrays of whatever is still alive.
import weakref as _weakref

_scope_pool = _weakref.WeakSet()


def _pool_register(scope):
    _scope_pool.add(scope)


def scope_pool_size() -> int:
    return len(_scope_pool)


def clear_scope_pool():
    """Drop every tracked scope's contents (device buffers become
    collectable) — reference ScopePool::Clear. The global scope is
    emptied but stays usable."""
    for s in list(_scope_pool):
        s._vars.clear()
        with s._pin_lock:
            s._views.clear()
            s._pinned.clear()
        s.drop_kids()


class Scope:
    def __init__(self, parent: Optional["Scope"] = None):
        self._vars: Dict[str, object] = {}
        self.parent = parent
        self._kids = []
        # Pinned views (the Executor's bound step): key -> {name: value}
        # gathered once from this scope, for state that the holder's
        # program never writes. `_pinned` maps each name some view
        # holds to those views' keys: a set/delete of one of THOSE
        # names drops the views that hold it, whoever writes (another
        # program's write-back too), and their holders gather again on
        # their next call; a write to any other name (the KV pools a
        # step writes back every call) costs one membership test and
        # drops nothing.
        self._views: Dict[object, Dict[str, object]] = {}
        self._pinned: Dict[str, set] = {}
        self._pin_lock = threading.Lock()
        _pool_register(self)

    def var(self, name):
        """Create-if-missing (scope.h:62 Var)."""
        if name not in self._vars:
            self._vars[name] = None
        return name

    def find_var(self, name):
        s = self
        while s is not None:
            if name in s._vars:
                return s._vars[name]
            s = s.parent
        return None

    def has(self, name):
        s = self
        while s is not None:
            if name in s._vars and s._vars[name] is not None:
                return True
            s = s.parent
        return False

    def set(self, name, value):
        self._vars[name] = value
        if name in self._pinned:
            self._unpin(name)

    def pin(self, key, names, convert):
        """Gather `names` from THIS scope (no parent walk: a scope with
        a parent is not pinned) into a view kept under `key` until one
        of them is set or deleted. `convert(value)` gives the form the
        holder computes with; where it differs the scope takes it too,
        as a step's write-back would. Raises KeyError(name) for a name
        that is missing or None.

        The write comes before the membership test in set(), and the
        names are registered here before they are read, both sides of
        the drop under one lock: a concurrent set() either lands before
        the gather or drops the view after it."""
        with self._pin_lock:
            for n in names:
                self._pinned.setdefault(n, set()).add(key)
            view = {}
            for n in names:
                v = self._vars.get(n)
                if v is None:
                    raise KeyError(n)
                c = convert(v)
                if c is not v:
                    self._vars[n] = c
                view[n] = c
            self._views[key] = view
            return view

    def pinned_view(self, key):
        """The view pin() left under `key`, or None once a pinned name
        was written."""
        return self._views.get(key)

    def drop_view(self, key):
        with self._pin_lock:
            for n in self._views.pop(key, ()):
                keys = self._pinned.get(n)
                if keys is not None:
                    keys.discard(key)
                    if not keys:
                        del self._pinned[n]

    def _unpin(self, name):
        with self._pin_lock:
            for key in self._pinned.pop(name, ()):
                self._views.pop(key, None)

    def get(self, name):
        v = self.find_var(name)
        if v is None:
            raise KeyError(f"var {name!r} not initialised in scope")
        return v

    def get_numpy(self, name) -> np.ndarray:
        return np.asarray(self.get(name))

    def new_scope(self) -> "Scope":
        kid = Scope(parent=self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids.clear()

    def names(self):
        return list(self._vars)

    def delete(self, name):
        self._vars.pop(name, None)
        if name in self._pinned:
            self._unpin(name)


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope() -> Scope:
    return _scope_stack[-1]


@contextlib.contextmanager
def scope_guard(scope: Scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()
