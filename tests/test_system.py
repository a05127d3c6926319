"""End-to-end behaviour of the LIDC system (the paper's workflow, Fig. 5)."""

import pytest

from repro.ckpt.checkpoint import latest_step
from repro.configs.base import ShapeConfig, get_config
from repro.core.jobs import JobSpec
from repro.core.strategy import CompletionTimeStrategy
from repro.core.scheduler import CompletionModel
from repro.runtime.executors import runs_real
from repro.runtime.fleet import build_fleet, resilient_run


def small_fleet(n=2, **kw):
    return build_fleet(n_clusters=n, chips=8, archs=["lidc-demo"],
                       ckpt_every=5, **kw)


def test_full_job_workflow():
    sys_ = small_fleet()
    h = sys_.client.run_job({"app": "train", "arch": "lidc-demo",
                             "shape": "custom", "chips": 4, "steps": 8})
    assert h is not None and h.state == "Completed"
    assert h.result["final_loss"] is not None
    assert h.result["real_compute"] is True
    # the receipt carried the paper's protocol fields
    assert "status_name" in h.receipt and "result_name" in h.receipt


def test_identical_request_served_from_cache():
    sys_ = small_fleet()
    fields = {"app": "train", "arch": "lidc-demo", "shape": "custom",
              "chips": 4, "steps": 6}
    h1 = sys_.client.run_job(fields)
    jobs_before = sum(len(c.jobs) for c in sys_.overlay.clusters.values())
    h2 = sys_.client.run_job(fields)
    jobs_after = sum(len(c.jobs) for c in sys_.overlay.clusters.values())
    assert h1.state == h2.state == "Completed"
    assert jobs_after == jobs_before          # no new job was spawned
    assert h2.result is not None


def test_validation_rejects_bad_jobs():
    sys_ = small_fleet()
    # unknown arch
    h = sys_.client.submit({"app": "train", "arch": "not-a-model",
                            "chips": 4, "steps": 1})
    assert h is None or h.state != "Completed"
    # the paper's example: malformed SRR id
    h2 = sys_.client.submit({"app": "blast", "srr": "banana"})
    assert h2 is None
    # too many chips
    h3 = sys_.client.submit({"app": "train", "arch": "lidc-demo",
                             "chips": 4096, "steps": 1})
    assert h3 is None


def test_status_protocol_states():
    sys_ = small_fleet()
    h = sys_.client.run_job({"app": "blast", "srr": "SRR2931415",
                             "db": "human", "mem": 4, "cpu": 2})
    assert h.state == "Completed"
    states = {s["state"] for s in h.status_history}
    assert states <= {"Pending", "Running", "Completed", "Failed"}
    assert h.result["output_bytes"] > 0


@pytest.mark.slow
def test_failover_resumes_from_named_checkpoint():
    sys_ = small_fleet()
    fields = {"app": "train", "arch": "lidc-demo", "shape": "custom",
              "chips": 4, "steps": 20, "tag": "failover-test"}
    spec = JobSpec(app="train",
                   fields={k: v for k, v in fields.items() if k != "app"})
    run_name = f"train-{spec.signature()}"

    killed = {"done": False}
    orig = sys_.lake.put_json

    def hook(name, obj, **kw):
        r = orig(name, obj, **kw)
        if ("ckpt" in str(name) and "latest" in str(name)
                and not killed["done"] and obj.get("step", 0) >= 10):
            killed["done"] = True
            sys_.overlay.fail_cluster(
                next(iter(sys_.overlay.clusters)))
        return r

    sys_.lake.put_json = hook
    h, attempts = resilient_run(sys_, fields)
    assert killed["done"], "failure injection never triggered"
    assert h.state == "Completed"
    assert attempts >= 2
    assert h.result["resumed_from"] is not None
    assert latest_step(sys_.lake, run_name) == 20


class _Device:
    """A device reporting ``bytes_limit`` (a chip) or no limit (the CPU)."""

    def __init__(self, limit):
        self.limit = limit

    def memory_stats(self):
        return None if self.limit is None else {"bytes_limit": self.limit}


@pytest.mark.parametrize("arch,limit,real", [
    ("qwen3-1.7b", 16e9, True),              # fits one 16 GB chip
    ("qwen3-1.7b", 2e9, False),              # its weights alone do not
    ("lidc-demo", None, True),               # no limit: small runs real
    ("mistral-large-123b", None, False),     # no limit: 123B stays simulated
])
def test_executor_fit_rule(arch, limit, real):
    shape = ShapeConfig("serve", "decode", 1024, 4)
    assert runs_real(get_config(arch), shape, _Device(limit)) is real


def test_serve_job_prompt_lengths_from_fields():
    sys_ = small_fleet()
    h = sys_.client.run_job({"app": "serve", "arch": "lidc-demo",
                             "plens": "1,20,9", "new_tokens": 3})
    assert h is not None and h.state == "Completed", h and h.error
    assert h.result["real_compute"] is True
    assert h.result["requests"] == 3 and h.result["tokens_out"] == 9


def test_cluster_join_during_operation():
    from repro.runtime.fleet import standard_endpoints
    from repro.runtime.executors import memory_model
    sys_ = small_fleet(n=1)
    sys_.overlay.fail_cluster("pod0")
    fields = {"app": "train", "arch": "lidc-demo", "shape": "custom",
              "chips": 4, "steps": 4}
    h = sys_.client.submit(fields)
    assert h is None or h.state != "Completed"
    # a new cluster joins the overlay — no controller to update
    sys_.add_cluster("latecomer", chips=8,
                     endpoints=standard_endpoints(["lidc-demo"]),
                     memory_model=memory_model)
    h2 = sys_.client.run_job(fields)
    assert h2 is not None and h2.state == "Completed"
    assert h2.result["cluster"] == "latecomer"


def test_completion_time_strategy_learns():
    model = CompletionModel()
    sys_ = build_fleet(n_clusters=2, chips=8, archs=["lidc-demo"],
                       strategy=CompletionTimeStrategy(model))
    fields = {"app": "blast", "srr": "SRR2931415", "db": "human",
              "mem": 4, "cpu": 2}
    h = sys_.client.run_job(fields)
    assert h.state == "Completed"
    # feed the observation back (the Table-I learning loop)
    spec_fields = {"app": "blast", "srr": "SRR2931415", "db": "human",
                   "mem": "4", "cpu": "2"}
    model.observe(spec_fields, face_id=1, duration=h.result["run_time_s"])
    assert model.predict(spec_fields, face_id=1) is not None


def test_blast_table1_cpu_mem_insensitivity():
    """The paper's central Table-I observation: varying cpu/mem barely
    changes run time (it is I/O-bound)."""
    sys_ = small_fleet()
    times = []
    for cpu, mem in [(2, 4), (4, 4), (2, 6)]:
        h = sys_.client.run_job({"app": "blast", "srr": "SRR2931415",
                                 "db": "human", "mem": mem, "cpu": cpu})
        times.append(h.result["run_time_s"])
    spread = (max(times) - min(times)) / max(times)
    assert spread < 0.05     # <5% variation, like Table I
