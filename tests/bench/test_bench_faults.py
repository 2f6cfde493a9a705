"""A run with the timed path broken underneath comes out not correct.

Each test drives the harness's run (everything but the look for a chip)
on CPU-sized cells, with one fault planted in the program: a step that
returns its state unchanged, an answer or a token altered where it is
produced, and (across four devices) the exchange between ranks left
out.  Half a batch left out is a training fault; no cell here trains.
"""

import os
import tempfile
import time

import jax
import jax.numpy as jnp
import pytest

import tiny
from bench import harness


def _run(root, workload):
    return harness.run_cell(root, workload, 2 ** 31 + 11, 0.3, False,
                            jax.devices()[:1], t_start=time.perf_counter(),
                            log=lambda msg: None)


@pytest.fixture(scope="module")
def root():
    return tiny.make_root(tempfile.mkdtemp())


def _faces_unchanged(monkeypatch):
    from repro.core.engine_persistent import PersistentEngine

    def unchanged(self, mem):
        self.stats.dispatches += 1
        r = jnp.sqrt(jnp.mean(jnp.square(mem["u"])))
        return dict(mem), jnp.full((self.n_iters,), r)

    monkeypatch.setattr(PersistentEngine, "__call__", unchanged)


def _faces_altered(monkeypatch):
    from repro.core.engine_persistent import PersistentEngine

    call = PersistentEngine.__call__

    def altered(self, mem):
        mem, red = call(self, mem)
        u = mem["u"]
        return dict(mem, u=u.at[0, 0, 0, 1, 2, 3].add(
            1e-3 * jnp.max(jnp.abs(u)))), red

    monkeypatch.setattr(PersistentEngine, "__call__", altered)


def _token_altered(monkeypatch):
    import importlib

    serve = importlib.import_module("repro.launch.serve")
    argmax = serve._argmax_tok
    vocab = tiny.QWEN["vocab_size"]
    monkeypatch.setattr(serve, "_argmax_tok",
                        lambda logits: (argmax(logits) + 1) % vocab)


def _cache_unchanged(monkeypatch):
    from repro.models import Model

    step = Model.decode_step

    def frozen(self, params, caches, token, **kw):
        logits, _ = step(self, params, caches, token, **kw)
        return logits, caches

    monkeypatch.setattr(Model, "decode_step", frozen)


FAULTS = {
    ("faces", "state unchanged"): _faces_unchanged,
    ("faces", "answer altered"): _faces_altered,
    ("serve", "token altered"): _token_altered,
    ("serve", "state unchanged"): _cache_unchanged,
}


@pytest.mark.parametrize("cell", ["faces", "serve"])
def test_sound_run_is_correct(root, cell):
    assert _run(root, cell)["correct"]


@pytest.mark.parametrize("cell,fault", sorted(FAULTS))
def test_fault_is_not_correct(root, monkeypatch, cell, fault):
    FAULTS[cell, fault](monkeypatch)
    assert not _run(root, cell)["correct"]


EXCHANGE = r'''
import sys, tempfile, time
sys.path[:0] = {paths!r}
import jax
import tiny
from bench import harness

mix = dict(tiny.FACES_MIX, grid=[2, 2, 1])
root = tiny.make_root(tempfile.mkdtemp(), faces_mix=mix, chips=4)

def run():
    return harness.run_cell(root, "faces", 5, 0.3, False, jax.devices(),
                            t_start=time.perf_counter(), log=lambda m: None)

print("sound", run()["correct"])
jax.lax.ppermute = lambda x, axis_name, perm: x   # nothing leaves its rank
print("no exchange", run()["correct"])
'''


def test_exchange_left_out_is_not_correct(subproc):
    here = os.path.dirname(os.path.abspath(__file__))
    repo = os.path.dirname(os.path.dirname(here))
    r = subproc(EXCHANGE.format(paths=[here, repo,
                                       os.path.join(repo, "src")]),
                devices=4, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "sound True" in r.stdout and "no exchange False" in r.stdout
