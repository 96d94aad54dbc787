"""Tracer hygiene inside jitted/scanned code (rule ``tracer-hygiene``).

The K-dispatch win (PR 5/7) holds only while the scanned window stays
on device: ONE implicit device→host sync inside the traced region —
``float(loss)``, ``loss.item()``, ``np.asarray(x)``, a ``print`` of a
traced value — re-serializes every dispatch on the host link and
silently erases the speedup (or worse, retraces per step).  Host-side
nondeterminism (``time.time``, ``random.*``, argless ``datetime.now``)
inside a traced function bakes a trace-time constant into the compiled
program, breaking the bitwise-twin contract between runs.

Traced scope is resolved statically per module:

* functions decorated with ``jax.jit`` / ``partial(jax.jit, ...)`` /
  ``jax.pmap``,
* functions wrapped by ``jax.jit(fn)`` calls (names resolve to local
  defs and ``self.<method>`` of the enclosing class; inline lambdas
  count),
* functions handed to ``lax.scan`` / ``lax.cond`` / ``lax.while_loop``
  / ``lax.fori_loop`` / ``lax.map`` / ``jax.vmap`` / ``shard_map``,
* **Pallas kernel bodies** — the function operand of ``pl.pallas_call``
  (a direct name, or a local ``kernel = functools.partial(fn, ...)``
  assignment, which the kernel modules' idiom uses).  A kernel body is
  the most traced scope there is: a host sync inside one doesn't just
  slow a dispatch, it breaks compilation on real hardware while
  silently "working" under ``interpret=True`` on CPU.  A kernel that
  reaches ``pallas_call`` through a helper's *parameter*
  (``_call(kernel, ...)`` where the helper forwards ``kernel`` into
  the call position) IS resolved, one call level deep: the helper's
  forwarding parameters are computed from its body, and the caller's
  matching argument (positional or keyword, directly or through
  ``partial``) is marked traced.  Remaining soundness limit: two or
  more levels of parameter indirection,
* anything lexically nested inside a traced function.

Only the hot-loop modules are scanned (``TARGET_FILES``): the contract
is about the trainer/decode dispatch path — and the Pallas kernel tier
— not utility code that lawfully mixes host and device work.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from .core import Finding, Module, Repo, dotted_name

RULES = ('tracer-hygiene',)

#: the dispatch-path modules whose traced regions carry the bitwise /
#: no-host-sync contract (doc/static_analysis.md)
TARGET_FILES = ('cxxnet_tpu/nnet/trainer.py',
                'cxxnet_tpu/nnet/execution.py',
                'cxxnet_tpu/serve/decode.py',
                'cxxnet_tpu/ops/pallas_kernels.py')

#: function-argument positions per wrapper.  lax combinators demand a
#: `lax` qualifier (``jax.tree.map`` is NOT ``lax.map``); jit/pmap/vmap
#: accept a `jax` qualifier or a bare name (``from jax import jit``).
_LAX_HOF = {'scan': (0,), 'cond': (1, 2), 'while_loop': (0, 1),
            'fori_loop': (2,), 'map': (0,), 'switch': None}
_JAX_WRAP = {'jit': (0,), 'pmap': (0,), 'vmap': (0,), 'shard_map': (0,)}


def _hof_positions(fname: str):
    parts = fname.split('.')
    leaf = parts[-1]
    if leaf in _LAX_HOF and 'lax' in parts[:-1]:
        return True, _LAX_HOF[leaf]
    if leaf in _JAX_WRAP and (len(parts) == 1 or parts[0] == 'jax'
                              or leaf == 'shard_map'):
        return True, _JAX_WRAP[leaf]
    # pl.pallas_call(kernel, ...) — the kernel operand runs fully traced
    # (Mosaic on TPU, the pallas interpreter on CPU)
    if leaf == 'pallas_call':
        return True, (0,)
    return False, None

_SYNC_BUILTINS = {'float', 'bool', 'int'}
_SYNC_ATTRS = {'item', 'tolist'}
_NP_SYNCS = {'np.asarray', 'np.array', 'numpy.asarray', 'numpy.array'}
_NONDET = {'time.time', 'time.monotonic', 'time.perf_counter',
           'time.time_ns', 'os.urandom', 'uuid.uuid4'}


def _is_jit_decorator(dec: ast.AST) -> bool:
    name = dotted_name(dec)
    if name and name.split('.')[-1] in ('jit', 'pmap'):
        return True
    if isinstance(dec, ast.Call):
        fname = dotted_name(dec.func) or ''
        if fname.split('.')[-1] in ('jit', 'pmap'):
            return True
        if fname.split('.')[-1] == 'partial' and dec.args:
            first = dotted_name(dec.args[0]) or ''
            if first.split('.')[-1] in ('jit', 'pmap'):
                return True
    return False


class _Scope:
    """Resolves which function defs in a module are traced."""

    def __init__(self, mod: Module):
        self.mod = mod
        self.traced: Set[ast.AST] = set()          # FunctionDef / Lambda
        self._local_defs: dict = {}                # (parent, name) -> def
        self._methods: dict = {}                   # (class, name) -> def
        self._assigns: dict = {}            # (parent, name) -> value expr
        self._fwd_cache: dict = {}   # helper def -> ((pos, name), ...)
        self._index(mod.tree, None, None)
        self._mark(mod.tree)

    def _index(self, node: ast.AST, parent: Optional[ast.AST],
               cls: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._local_defs[(parent, child.name)] = child
                self._index(child, child, cls)
            elif isinstance(child, ast.ClassDef):
                for sub in child.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        self._methods[(child.name, sub.name)] = sub
                self._index(child, parent, child.name)
            else:
                if isinstance(child, ast.Assign) \
                        and len(child.targets) == 1 \
                        and isinstance(child.targets[0], ast.Name):
                    # kernel = functools.partial(_fn, ...) — the kernel
                    # modules' pallas_call idiom; last assignment wins
                    self._assigns[(parent, child.targets[0].id)] = \
                        child.value
                self._index(child, parent, cls)

    def _resolve(self, arg: ast.AST, fn_parent: Optional[ast.AST],
                 cls: Optional[str], _depth: int = 0) -> Optional[ast.AST]:
        if _depth > 8:                   # assignment-chain cycle guard
            return None
        if isinstance(arg, ast.Lambda):
            return arg
        if isinstance(arg, ast.Call):
            # functools.partial(fn, ...): the wrapped fn is the operand
            fname = dotted_name(arg.func) or ''
            if fname.split('.')[-1] == 'partial' and arg.args:
                return self._resolve(arg.args[0], fn_parent, cls,
                                     _depth + 1)
            return None
        if isinstance(arg, ast.Name):
            # walk outward through enclosing function scopes
            parent = fn_parent
            while True:
                d = self._local_defs.get((parent, arg.id))
                if d is not None:
                    return d
                a = self._assigns.get((parent, arg.id))
                if a is not None:
                    return self._resolve(a, parent, cls, _depth + 1)
                if parent is None:
                    return None
                parent = next((p for (p, n), v in self._local_defs.items()
                               if v is parent), None)
        name = dotted_name(arg)
        if name and name.startswith('self.') and cls is not None:
            return self._methods.get((cls, name[5:]))
        return None

    def _forwarded_params(self, helper: ast.AST):
        """Parameters of ``helper`` that flow into a traced HOF position
        inside its own body — the ``_call(kernel, ...)`` indirection:
        a helper taking ``kernel`` and forwarding it into
        ``pl.pallas_call(kernel, ...)`` (directly or via ``partial``)
        makes the CALLER's matching argument a traced function.  One
        level only: a helper forwarding into another helper is the
        documented remaining limit.  Returns ``((position, name), ...)``.
        """
        cached = self._fwd_cache.get(helper)
        if cached is not None:
            return cached
        names: Set[str] = set()
        for node in ast.walk(helper):
            if not isinstance(node, ast.Call):
                continue
            is_hof, idxs = _hof_positions(dotted_name(node.func) or '')
            if not is_hof:
                continue
            args = range(len(node.args)) if idxs is None else idxs
            for i in args:
                if i >= len(node.args):
                    continue
                a = node.args[i]
                if isinstance(a, ast.Call):
                    # partial(kernel, ...) in the HOF position
                    fname = dotted_name(a.func) or ''
                    if fname.split('.')[-1] == 'partial' and a.args:
                        a = a.args[0]
                if isinstance(a, ast.Name):
                    names.add(a.id)
        pos = helper.args.posonlyargs + helper.args.args
        out = tuple((j, a.arg) for j, a in enumerate(pos)
                    if a.arg in names)
        self._fwd_cache[helper] = out
        return out

    def _mark(self, tree: ast.AST) -> None:
        # decorators
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(_is_jit_decorator(d) for d in node.decorator_list):
                    self.traced.add(node)
        # wrapper calls: jax.jit(fn), lax.scan(body, ...), jax.vmap(f)...
        def walk(node, fn_parent, cls):
            for child in ast.iter_child_nodes(node):
                nparent, ncls = fn_parent, cls
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    nparent = child
                elif isinstance(child, ast.ClassDef):
                    ncls = child.name
                if isinstance(child, ast.Call):
                    fname = dotted_name(child.func) or ''
                    is_hof, idxs = _hof_positions(fname)
                    if is_hof:
                        args = (range(len(child.args)) if idxs is None
                                else idxs)
                        for i in args:
                            if i < len(child.args):
                                t = self._resolve(child.args[i],
                                                  fn_parent, cls)
                                if t is not None:
                                    self.traced.add(t)
                    else:
                        # helper indirection: _call(kernel, ...)
                        # where the helper forwards a parameter into a
                        # HOF position — the caller's argument is traced
                        helper = self._resolve(child.func, fn_parent, cls)
                        if isinstance(helper, (ast.FunctionDef,
                                               ast.AsyncFunctionDef)):
                            for j, pname in self._forwarded_params(helper):
                                a = child.args[j] \
                                    if j < len(child.args) else next(
                                        (kw.value for kw in child.keywords
                                         if kw.arg == pname), None)
                                if a is None:
                                    continue
                                t = self._resolve(a, fn_parent, cls)
                                if t is not None:
                                    self.traced.add(t)
                walk(child, nparent, ncls)
        walk(tree, None, None)
        # closure: nested defs/lambdas inside traced fns are traced
        changed = True
        while changed:
            changed = False
            for t in list(self.traced):
                body = t.body if isinstance(t.body, list) else [t.body]
                for stmt in body:
                    for sub in ast.walk(stmt):
                        if isinstance(sub, (ast.FunctionDef,
                                            ast.AsyncFunctionDef,
                                            ast.Lambda)):
                            if sub not in self.traced:
                                self.traced.add(sub)
                                changed = True


def _iter_own_nodes(fn: ast.AST):
    """Walk a function body but stop at nested def/lambda boundaries —
    nested functions of a traced fn are traced themselves and get their
    own visit, so every violation is reported exactly once, at the
    innermost function that contains it."""
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    stack = list(body)
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            stack.append(child)


def _check_traced_body(mod: Module, fn: ast.AST,
                       out: List[Finding]) -> None:
    label = getattr(fn, 'name', '<lambda>')
    for node in _iter_own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted_name(node.func) or ''
            leaf = fname.split('.')[-1]
            msg = None
            if isinstance(node.func, ast.Name) and \
                    node.func.id in _SYNC_BUILTINS:
                msg = (f'{node.func.id}() on a traced value forces a '
                       f'device->host sync inside {label}')
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _SYNC_ATTRS and not node.args:
                msg = (f'.{node.func.attr}() forces a device->host sync '
                       f'inside traced {label}')
            elif fname in _NP_SYNCS:
                msg = (f'{fname}() materializes a traced value on host '
                       f'inside {label}')
            elif isinstance(node.func, ast.Name) and \
                    node.func.id == 'print':
                msg = (f'print() of traced values syncs and retraces '
                       f'inside {label} (use jax.debug.print)')
            elif fname in _NONDET:
                msg = (f'{fname}() inside traced {label} bakes a '
                       f'trace-time constant into the compiled program')
            elif fname.startswith('random.') or \
                    fname.startswith('np.random.') or \
                    fname.startswith('numpy.random.'):
                msg = (f'{fname}() inside traced {label} is host '
                       f'nondeterminism — derive a jax.random key')
            elif fname.endswith('datetime.now') or fname == 'datetime.now':
                if not node.args and not node.keywords:
                    msg = (f'argless datetime.now() inside traced '
                           f'{label} is a trace-time constant')
            if msg is not None:
                out.append(Finding('tracer-hygiene', mod.rel,
                                   node.lineno, msg))


def check_module(mod: Module) -> List[Finding]:
    scope = _Scope(mod)
    findings: List[Finding] = []
    for fn in sorted(scope.traced, key=lambda f: f.lineno):
        _check_traced_body(mod, fn, findings)
    findings.sort(key=lambda f: f.line)
    return findings


def run(repo: Repo) -> List[Finding]:
    findings: List[Finding] = []
    for rel in TARGET_FILES:
        if repo.has(rel):
            findings.extend(check_module(repo.module(rel)))
    return findings
