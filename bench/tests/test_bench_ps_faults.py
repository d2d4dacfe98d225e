"""A run with the timed path broken underneath comes out not correct."""
import pytest

from bench.tests import ps_tiny


def receive_nothing(monkeypatch):
    from repro.core import server

    monkeypatch.setattr(server, "receive",
                        lambda state, msg: state._replace(t=state.t + 1))


def half_batch(monkeypatch):
    from repro.core import async_sim

    whole = async_sim.client_step_fn

    def client_step_fn(strategy, grad_fn, space):
        def half(params, batch):
            x, y = batch
            return grad_fn(params, (x[:x.shape[0] // 2], y[:y.shape[0] // 2]))
        return whole(strategy, half, space)

    monkeypatch.setattr(async_sim, "client_step_fn", client_step_fn)


@pytest.mark.parametrize("fault", [receive_nothing, half_batch])
@pytest.mark.parametrize("workload", ps_tiny.CELLS)
def test_broken_step_is_not_correct(monkeypatch, fault, workload):
    fault(monkeypatch)
    line = ps_tiny.run(workload, seed=5)
    assert not line["correct"], line["checks"]
