"""The compiled step's operations, by the program's own scopes.

A profiler event of the chip's ``XLA Ops`` line is named by its HLO line
WITHOUT ``metadata={op_name=...}``, so a trace alone cannot say which module
or ``jax.named_scope`` an operation came from. The process that compiled the
program can: this module keeps, for a program it is told of, a handle from
which the compiled text can be had again, and makes from it, when asked, the
table ``{HLO instruction: (scope path, pass)}`` that lays a trace's device
time on the program's scopes (``benchmark/parts.py`` is the reader).

- :func:`note` keeps ``jitted.trace(*signature)`` under the program's name, one
  slot a name. The signature is the arguments' shapes, dtypes and, for a
  COMMITTED array, its sharding: only then does compiling it again find the
  first compile's entry in JAX's persistent cache. The handle is a jaxpr (a
  copy of the trace's: :func:`_unpinned`): it holds no device buffer, no
  executable, and keeps no argument alive.
- :func:`op_scopes` lowers and compiles the handle, reads the text, lets the
  executable go and memoises the table, inside one run-log span
  ``program.op_scopes`` (``args``: ``fun``, ``instructions``, ``cache``, and
  ``code_bytes``: what the executable held on the device while its text was
  read). Never free (JAX lowers again and asks the backend again), so
  nothing calls it on a hot path. Where a persistent cache is on and the
  compile was NOT a hit, the text is another compile's, whose operations
  need not be numbered like the ones that ran: the answer is ``None``, said
  on stderr.
- :func:`part_of` is the one reading of an ``op_name``.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys

import jax

from distkeras_tpu.observability import trace

__all__ = ["note", "op_scopes", "part_of", "parse_hlo", "save"]

#: name -> ``jax.stages.Traced`` of the program last noted under it
_handles: dict = {}
#: name -> the table made from that handle (``None``: made, and not to be used)
_tables: dict = {}


def _signature(a):
    if isinstance(a, jax.Array) and a.committed:
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def _unpinned(traced):
    """The same handle on a COPY of its closed jaxpr. JAX keeps a lowering, and
    the executable loaded from it, for as long as the jaxpr it was made from
    lives (``pjit._pjit_lower`` is a cache with weak keys), so the trace's own
    jaxpr would keep the step's code on the device after its trainer is gone.
    The copy lowers to the same module; the equations are shared, not copied."""
    closed = traced.jaxpr
    return type(traced)(
        traced._meta_tys_flat,
        dict(traced._params, jaxpr=type(closed)(closed.jaxpr, closed.consts)),
        traced._in_tree, traced.out_tree, traced._consts)


def note(name: str, jitted, args) -> object:
    """Keep a handle on the program ``jitted`` ran for ``args`` (arrays, deleted
    by donation or not), under ``name``: what the run log's ``jax.compile``
    entries and a trace's ``XLA Modules`` line call it, less ``jit_``. Right
    after the call that compiled it the trace is cached and this costs under
    a millisecond. Returns the handle."""
    traced = _unpinned(jitted.trace(*jax.tree.map(_signature, args)))
    _handles[name] = traced
    _tables.pop(name, None)
    return traced


def _cache_verdict(before: dict, after: dict):
    if after["cache_hits"] > before["cache_hits"]:
        return "hit"
    if after["cache_misses"] > before["cache_misses"]:
        return "miss"
    return None        # no persistent cache answered: it is off


def op_scopes(name: str):
    """``{instruction: (path, pass)}`` of the program noted as ``name``: every
    instruction of every computation of its compiled text (:func:`parse_hlo`).
    ``None`` where no such program was noted, or where the compile that gave
    the text was not the one that ran (module doc)."""
    if name in _tables:
        return _tables[name]
    traced = _handles.get(name)
    if traced is None:
        return None
    before = trace.jax_counts()
    with trace.span("program.op_scopes", cat="program", log=True,
                    args={"fun": name}) as sp:
        # on one more copy: the lowering and the executable are cached under
        # the jaxpr they were made from, and go with it
        compiled = _unpinned(traced).lower().compile()
        text = compiled.as_text()
        analysis = compiled.memory_analysis()
        del compiled
        cache = _cache_verdict(before, trace.jax_counts())
        table = parse_hlo(text)
        sp.args.update(
            instructions=len(table), cache=cache,
            code_bytes=getattr(analysis, "generated_code_size_in_bytes", None))
    if cache == "miss":
        print(f"program.op_scopes: compiling {name} again MISSED the persistent "
              f"cache, so this text is not the program that ran; no table",
              file=sys.stderr)
        table = None
    _tables[name] = table
    return table


# -- one reading of an op_name ---------------------------------------------------

#: transformation wrappers: what stands in their brackets is path
_WRAPPER = re.compile(r"^(jvp|transpose|vmap)\((.*)\)$")
#: components that are how JAX spells a call, a loop or remat, not a scope
_DROPPED = re.compile(
    r"^(jit\(.*\)|pjit|checkpoint|rematted_computation|remat2?|while|body|cond|"
    r"branch_\d+_fun|closed_call|custom_[jv][vj]p_call(_jaxpr)?)$")
_LAYER = re.compile(r"\bblocks_\d+\b")
REMAT_MARKER = "rematted_computation"


def part_of(op_name: str):
    """``(path, pass)`` of one ``op_name``.

    ``jit(train_step)/transpose(jvp(TransformerLM.hidden))/checkpoint/
    rematted_computation/blocks_3/attn/mla_latent/mul`` is path ``("blocks_*",
    "attn", "mla_latent")``, pass ``"remat"``. Wrappers (``jvp(..)``,
    ``transpose(..)``, ``vmap(..)``) are unwrapped: ``jvp(attn)/dot_general``
    is under ``attn``. Dropped: the primitive at the end; ``jit(..)`` and the
    other spellings of a call, a loop and remat; a component whose module
    starts with a capital (flax names a module nobody named by its class:
    ``TransformerLM.hidden``). ``blocks_<i>`` becomes ``blocks_*``. A flax
    method other than ``__call__`` is a component of its own
    (``blocks_*._attn_full``). Pass: ``remat`` under ``rematted_computation``,
    else ``backward`` under ``transpose(``, else ``forward``. Where the
    compiler joined several names with ``;`` the first is read.

    A name with no ``/`` is no scope path: it is the compiler's own name for
    what it made in place of the program's operation (``ragged-dot-none``, the
    TPU's grouped product; ``sort``; ``gather``) or one of the program's
    arguments (``params['blocks_0']...``). Its path is that name, and it has
    no pass (``""``): the program's scope was lost with the operation."""
    first = op_name.split(";", 1)[0]
    if "/" not in first:
        return (_LAYER.sub("blocks_*", first),), ""
    which = ("remat" if REMAT_MARKER in first
             else "backward" if "transpose(" in first else "forward")
    path = []
    for part in first.split("/")[:-1]:
        hit = _WRAPPER.match(part)
        while hit:
            part = hit.group(2)
            hit = _WRAPPER.match(part)
        if not part or part[0].isupper() or _DROPPED.match(part):
            continue
        path.append(_LAYER.sub("blocks_*", part))
    return tuple(path), which


# -- the compiled text -----------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_PRODUCT = re.compile(r" (?:convolution|dot)\(")
_CALL = re.compile(r" call\(")
NO_NAME = ((), "")      # an instruction the compiler gave no op_name


def parse_hlo(text: str) -> dict:
    """``{instruction: (path, pass)}`` over every instruction of every
    computation of an HLO module's text (the loss's and the kernels' loops are
    ``while`` bodies, whose events a trace names like any other).

    An instruction that calls a computation (a ``fusion``, an async pair or a
    ``call``, through ``calls=`` / ``to_apply=``): if the called computation
    holds a product (``convolution``, ``dot``) that has an ``op_name``, it is
    the first such product's; else the part most of its own and the called
    computation's ``op_name``s read as (a tie: the first seen). Any other
    instruction reads its own ``op_name``; one without is :data:`NO_NAME`."""
    members: dict = collections.defaultdict(list)    # computation -> op_names
    product: dict = {}                               # computation -> op_name
    rows, current = [], None
    for line in text.splitlines():
        if not line.startswith(" "):
            head = _COMPUTATION.match(line)
            current = head.group(1) if head else None
            continue
        hit = _INSTRUCTION.match(line)
        if not hit:
            continue
        op = _OP_NAME.search(line)
        op = op.group(1) if op else None
        called = _CALLS.search(line)
        if called is None and _CALL.search(line):
            called = _TO_APPLY.search(line)
        rows.append((hit.group(1), op, called.group(1) if called else None))
        if op and current:
            members[current].append(op)
            if current not in product and _PRODUCT.search(line):
                product[current] = op
    table = {}
    for name, op, called in rows:
        if called is not None and called in product:
            table[name] = part_of(product[called])
        elif called is not None:
            votes = collections.Counter(
                part_of(n) for n in ([op] if op else []) + members.get(called, []))
            table[name] = votes.most_common(1)[0][0] if votes else NO_NAME
        else:
            table[name] = part_of(op) if op else NO_NAME
    return table


# -- a table on disk ---------------------------------------------------------------


def save(name: str, directory: str):
    """Write :func:`op_scopes` of ``name`` as ``<directory>/op_scopes.<name>.json``
    (``{"program", "parts": [[path, pass], ...], "ops": {instruction: index}}``:
    what ``python3 benchmark/parts.py <trace dir> <file>`` reads beside a
    profiler trace of the same run). Returns the path, or ``None`` where there
    is no table to write."""
    table = op_scopes(name)
    if table is None:
        return None
    index: dict = {}
    ops = {op: index.setdefault(part, len(index)) for op, part in table.items()}
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"op_scopes.{name}.json")
    with open(path, "w") as f:
        json.dump({"program": name, "parts": [[list(p), w] for p, w in index],
                   "ops": ops}, f)
    return path
