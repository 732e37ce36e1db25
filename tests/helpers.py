"""Shared test helpers: numpy reference reductions, seeded input
generation, and the master+slave-threads socket harness."""

import base64
import hashlib
import re
import threading
from pathlib import Path

import numpy as np

# repo root for subprocess-based tests (cwd-independent)
REPO_ROOT = str(Path(__file__).resolve().parents[1])

# single source of truth for the numpy oracle: the check programs' module
from ytk_mp4j_tpu.check._oracle import NP_REF, expected_reduce  # noqa: F401
from ytk_mp4j_tpu.comm.master import Master
from ytk_mp4j_tpu.comm.process_comm import ProcessCommSlave


def make_inputs(n, length, operand, rng):
    if operand.dtype.kind == "f":
        return [rng.standard_normal(length).astype(operand.dtype)
                for _ in range(n)]
    return [rng.integers(1, 4, length).astype(operand.dtype)
            for _ in range(n)]


def run_slaves(n, fn, timeout=60.0, **slave_kwargs):
    """Start a master + n slave threads; fn(slave, rank) runs per rank.
    Returns per-rank results; raises the first slave error; asserts the
    master's aggregate exit code is 0. ``slave_kwargs`` are forwarded to
    every ProcessCommSlave (e.g. native_transport=False)."""
    master = Master(n, timeout=timeout).serve_in_thread()
    results = [None] * n
    errors = []

    def worker():
        slave = None
        try:
            slave = ProcessCommSlave("127.0.0.1", master.port,
                                     timeout=timeout, **slave_kwargs)
            results[slave.rank] = fn(slave, slave.rank)
            slave.close(0)
        except Exception as e:  # pragma: no cover - surfaced via errors
            errors.append(e)
            if slave is not None:
                try:
                    slave.close(1)
                except Exception:
                    pass

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "slave thread hung"
    if errors:
        raise errors[0]
    master.join(timeout)
    assert master.final_code == 0
    return results


def _kernel_without_locations(match) -> str:
    """A Mosaic kernel's serialized module (``custom_call_config.body``,
    MLIR bytecode in base64) as the digest of its text printed without
    locations: the bytecode holds every operation's Python call stack,
    which a ``with jax.named_scope(...)`` line in the caller changes and
    the kernel's code does not depend on."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        asm = module.operation.get_asm(enable_debug_info=False)
    return '"body":"sha256:%s"' % hashlib.sha256(asm.encode()).hexdigest()


def program_without_provenance(text: str) -> str:
    """An optimised module's text (``compiled.as_text()``) less what only
    says where an instruction came from: every ``metadata={...}``, the
    tables of files, functions and stack frames that ``stack_frame_id``
    points into (the CPU compiler's text has them between the header and
    the first computation), the locations inside a Mosaic kernel's body,
    and the instructions' own names (one inlined from a jitted helper is
    NAMED after its name stack: ``%jvp_jit_triu__.4`` under no scope,
    ``%jit_triu_.4`` under ``jvp(ffm.pairs)``), each replaced by its
    order of first appearance. Two programs with one such text are one
    program."""
    text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", text,
                  count=1, flags=re.S)
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = re.sub(r'"body":"([A-Za-z0-9+/=]+)"', _kernel_without_locations,
                  text)
    order: dict[str, str] = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: order.setdefault(m.group(0), f"%{len(order)}"),
                  text)


def watch_live_arrays(monkeypatch, trainer):
    """From now on every scoring program ``trainer`` builds notes, at
    each launch, the bytes of the largest device array that was not
    there when this was called (``jax.live_arrays()``: pieces in flight,
    margins, the model, and a table if anything built one). Returns the
    list the notes go to."""
    import jax

    before = {id(x) for x in jax.live_arrays()}
    seen = []
    build = trainer._build_score

    def watched(*args, **kw):
        program = build(*args, **kw)

        def launch(*operands):
            seen.append(max(x.nbytes for x in jax.live_arrays()
                            if id(x) not in before))
            return program(*operands)
        return launch

    monkeypatch.setattr(trainer, "_build_score", watched)
    return seen
