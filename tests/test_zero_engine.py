"""ZeroOneEngine: TPU-native ZeRO-1 optimizer-state sharding
(determined_tpu/pytorch/zero.py), unit + 2-process e2e.

Reference semantics: deepspeed ZeRO stage 1 as configured by
examples/deepspeed/gpt_neox/zero1.yaml — partitioned optimizer state,
full-parameter replicas, averaged gradients.
"""

import json
import os
import socket
import subprocess
import sys

import torch

from determined_tpu.pytorch import ZeroOneEngine
from determined_tpu.pytorch.zero import _partition

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed=0):
    torch.manual_seed(seed)
    return torch.nn.Sequential(
        torch.nn.Linear(8, 16), torch.nn.ReLU(), torch.nn.Linear(16, 1))


class TestSingleProcess:
    def test_matches_plain_optimizer(self):
        """World size 1: ZeRO-1 degenerates to plain grad accumulation —
        final params must match a hand-rolled AdamW loop exactly."""
        torch.manual_seed(0)
        x = torch.randn(32, 8)
        y = torch.randn(32, 1)

        ref = _mlp()
        ref_opt = torch.optim.AdamW(ref.parameters(), lr=1e-2)
        for step in range(4):
            for micro in range(2):
                i = (step * 2 + micro) * 4
                loss = torch.nn.functional.mse_loss(ref(x[i:i + 4]), y[i:i + 4])
                (loss / 2).backward()
            ref_opt.step()
            ref_opt.zero_grad(set_to_none=True)

        eng = ZeroOneEngine(
            _mlp(), lambda p: torch.optim.AdamW(p, lr=1e-2),
            micro_batch_size=4, gradient_accumulation=2)
        for step in range(4):
            for micro in range(2):
                i = (step * 2 + micro) * 4
                loss = torch.nn.functional.mse_loss(
                    eng(x[i:i + 4]), y[i:i + 4])
                eng.backward(loss)
                eng.step()

        for pr, pe in zip(ref.parameters(), eng.module.parameters()):
            assert torch.allclose(pr, pe, atol=1e-7), (pr, pe)

    def test_save_load_roundtrip(self, tmp_path):
        eng = ZeroOneEngine(
            _mlp(), lambda p: torch.optim.AdamW(p, lr=1e-2),
            micro_batch_size=4, gradient_accumulation=1)
        x, y = torch.randn(8, 8), torch.randn(8, 1)
        for _ in range(3):
            loss = torch.nn.functional.mse_loss(eng(x), y)
            eng.backward(loss)
            eng.step()
        eng.save_checkpoint(str(tmp_path), tag="t")

        eng2 = ZeroOneEngine(
            _mlp(seed=1), lambda p: torch.optim.AdamW(p, lr=1e-2),
            micro_batch_size=4, gradient_accumulation=1)
        eng2.load_checkpoint(str(tmp_path), tag="t")
        for a, b in zip(eng.module.parameters(), eng2.module.parameters()):
            assert torch.equal(a, b)
        assert eng2.optimizer_state_numel() == eng.optimizer_state_numel()

    def test_mixed_dtype_grads_bucketed_separately(self):
        """bf16 + fp32 params in one model: the flat buckets must group by
        dtype or torch.cat dies. Driven with a duck-typed dist (identity
        all_reduce / broadcast) so no process group is needed."""

        class FakeDist:
            def __init__(self):
                self.reduced = []
                self.broadcasts = []

            def all_reduce(self, t):
                self.reduced.append(t.dtype)

            def broadcast(self, t, src):
                self.broadcasts.append((t.dtype, src))

        model = torch.nn.Sequential(
            torch.nn.Linear(4, 4), torch.nn.Linear(4, 1))
        model[1].to(torch.bfloat16)
        eng = ZeroOneEngine(
            model, lambda p: torch.optim.SGD(p, lr=0.1),
            micro_batch_size=1, gradient_accumulation=1)
        eng._world = 2  # force the collective paths
        for p in eng._params:
            p.grad = torch.zeros_like(p)
        fake = FakeDist()
        eng._allreduce_grads(fake)
        assert set(fake.reduced) == {torch.float32, torch.bfloat16}
        eng._rebroadcast_params(fake)
        assert {d for d, _ in fake.broadcasts} == \
            {torch.float32, torch.bfloat16}
        # the flat-bucket reason: fewer collectives than tensors
        assert len(fake.broadcasts) < len(eng._params)

    def test_partition_balance_and_determinism(self):
        params = [torch.nn.Parameter(torch.zeros(n))
                  for n in (100, 90, 80, 10, 10, 10)]
        owners = _partition(list(params), 2)
        assert owners == _partition(list(params), 2)  # deterministic
        loads = [0, 0]
        for p, o in zip(params, owners):
            loads[o] += p.numel()
        assert abs(loads[0] - loads[1]) <= 90, loads  # roughly balanced
        assert set(owners) == {0, 1}


def test_zero1_two_process_e2e(tmp_path):
    """Real 2-process gloo run through the launch layer: partitioned
    optimizer state, owner-rebroadcast parameter sync, engine-sharded
    save/load (asserts live in the fixture)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(
        os.environ,
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
        DET_TORCH_MASTER_PORT=str(port),
    )
    r = subprocess.run(
        [sys.executable, "-m", "determined_tpu.launch.torch_distributed",
         "--nproc-per-node", "2", "--",
         sys.executable,
         os.path.join(REPO, "tests", "fixtures", "torch_dist",
                      "train_zero1.py"),
         str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    reports = {}
    for rank in (0, 1):
        with open(tmp_path / f"zero_rank{rank}.json") as f:
            reports[rank] = json.load(f)
    assert reports[0]["steps"] == reports[1]["steps"] == 4
    # each rank holds a real, non-trivial share of the optimizer state
    assert reports[0]["opt_state_numel"] > 0
    assert reports[1]["opt_state_numel"] > 0
    # chief-only platform reporting
    assert reports[0]["n_checkpoints"] >= 1
    assert reports[1]["n_checkpoints"] == 0
