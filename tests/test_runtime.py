"""Agent runtime tests: main loop, ordered commit, error routing, composite
chains (reference AgentRunnerTest / AgentRecordTrackerTest / ErrorHandlingTest
analogues, SURVEY §4 tier-1)."""

import asyncio

import pytest

from langstream_tpu.api.agent import BadRecordError, ProcessorResult, SingleRecordProcessor
from langstream_tpu.api.doc import ConfigModel
from langstream_tpu.api.record import Record, SimpleRecord
from langstream_tpu.api.agent import ComponentType
from langstream_tpu.core.parser import ModelBuilder
from langstream_tpu.core.registry import REGISTRY, AgentTypeInfo
from langstream_tpu.runtime.local_runner import LocalApplicationRunner


def make_app(pipeline_yaml: str, instance_yaml: str = "instance:\n  streamingCluster:\n    type: memory\n"):
    return ModelBuilder.build_application_from_files(
        {"pipeline.yaml": pipeline_yaml}, instance_text=instance_yaml
    ).application


class UpperProcessor(SingleRecordProcessor):
    async def process_record(self, record: Record) -> list[Record]:
        return [SimpleRecord.copy_from(record, value=str(record.value).upper())]


class ExplodeProcessor(SingleRecordProcessor):
    """Splits comma-separated values into multiple records."""

    async def process_record(self, record: Record) -> list[Record]:
        return [
            SimpleRecord.copy_from(record, value=part)
            for part in str(record.value).split(",")
        ]


class FailNTimesProcessor(SingleRecordProcessor):
    fails_left = {}

    async def init(self, configuration):
        self._fail_values = set(configuration.get("fail-values", []))
        self._times = int(configuration.get("times", 1000))

    async def process_record(self, record: Record) -> list[Record]:
        if record.value in self._fail_values:
            left = FailNTimesProcessor.fails_left.setdefault(record.value, self._times)
            if left > 0:
                FailNTimesProcessor.fails_left[record.value] = left - 1
                raise ValueError(f"boom on {record.value}")
        return [record]


class BadRecordProcessor(SingleRecordProcessor):
    async def init(self, configuration):
        self._bad = set(configuration.get("bad-values", []))

    async def process_record(self, record: Record) -> list[Record]:
        if record.value in self._bad:
            raise BadRecordError(f"bad record {record.value}")
        return [record]


def _register_test_agents():
    for type_, cls in [
        ("upper", UpperProcessor),
        ("explode", ExplodeProcessor),
        ("fail-n-times", FailNTimesProcessor),
        ("bad-record", BadRecordProcessor),
    ]:
        REGISTRY.register_agent(
            AgentTypeInfo(
                type=type_,
                component_type=ComponentType.PROCESSOR,
                factory=cls,
                composable=True,
                config_model=ConfigModel(type=type_, allow_unknown=True),
            )
        )


_register_test_agents()


async def run_app(pipeline, produce, expect_topic, expect_n, timeout=5.0, pre_stop=None):
    app = make_app(pipeline)
    runner = LocalApplicationRunner("test-app", app)
    await runner.run()
    for topic, value, key in produce:
        await runner.produce(topic, value, key=key)
    try:
        records = await runner.consume(expect_topic, expect_n, timeout=timeout)
    finally:
        if pre_stop:
            pre_stop(runner)
        await runner.stop()
    return records, runner


def test_end_to_end_pipeline(run):
    pipeline = """
id: p
topics:
  - name: in-t
    creation-mode: create-if-not-exists
  - name: out-t
    creation-mode: create-if-not-exists
pipeline:
  - type: upper
    id: up
    input: in-t
    output: out-t
"""

    async def main():
        records, _ = await run_app(
            pipeline, [("in-t", "hello", None), ("in-t", "world", None)], "out-t", 2
        )
        assert sorted(r.value for r in records) == ["HELLO", "WORLD"]

    run(main())


def test_fused_chain_end_to_end(run):
    pipeline = """
id: p
topics:
  - name: in-t
    creation-mode: create-if-not-exists
  - name: out-t
    creation-mode: create-if-not-exists
pipeline:
  - type: explode
    id: ex
    input: in-t
  - type: upper
    id: up
  - type: identity
    id: idn
    output: out-t
"""

    async def main():
        records, runner = await run_app(
            pipeline, [("in-t", "a,b,c", None)], "out-t", 3
        )
        assert sorted(r.value for r in records) == ["A", "B", "C"]
        # fused into a single physical agent
        assert len(runner.runners) == 1
        info = runner.agents_info()[0]
        assert info["records-in"] == 1
        assert info["records-out"] == 3

    async def check_commit():
        app = make_app(pipeline)
        runner = LocalApplicationRunner("t2", app)
        await runner.run()
        await runner.produce("in-t", "x,y")
        await runner.wait_for_records_out("ex", 2)
        await runner.stop()

    run(main())
    run(check_commit())


def test_source_commit_after_sink_write(run):
    """Ordered commit: the source offset advances only after all sink writes."""
    pipeline = """
id: p
topics:
  - name: in-t
    creation-mode: create-if-not-exists
  - name: out-t
    creation-mode: create-if-not-exists
pipeline:
  - type: explode
    id: ex
    input: in-t
    output: out-t
"""

    async def main():
        app = make_app(pipeline)
        runner = LocalApplicationRunner("app", app)
        await runner.run()
        await runner.produce("in-t", "1,2,3")
        await runner.consume("out-t", 3)
        await runner.wait_for_records_out("ex", 3)
        # after drain, consumer committed offset must be 1
        agent = runner.runners[0]
        await agent.wait_for_no_pending_records()
        info = agent.source.consumer.get_info()
        assert info["committed"]["0"] == 1
        await runner.stop()

    run(main())


def test_errors_skip(run):
    pipeline = """
id: p
topics:
  - name: in-t
    creation-mode: create-if-not-exists
  - name: out-t
    creation-mode: create-if-not-exists
errors:
  on-failure: skip
  retries: 0
pipeline:
  - type: bad-record
    id: br
    input: in-t
    output: out-t
    configuration:
      bad-values: ["poison"]
"""

    async def main():
        records, runner = await run_app(
            pipeline,
            [("in-t", "ok1", None), ("in-t", "poison", None), ("in-t", "ok2", None)],
            "out-t",
            2,
        )
        assert sorted(r.value for r in records) == ["ok1", "ok2"]
        info = runner.agents_info()[0]
        assert info["failures"] == 1

    run(main())


def test_errors_retry_then_success(run):
    FailNTimesProcessor.fails_left.clear()
    pipeline = """
id: p
topics:
  - name: in-t
    creation-mode: create-if-not-exists
  - name: out-t
    creation-mode: create-if-not-exists
errors:
  on-failure: fail
  retries: 3
pipeline:
  - type: fail-n-times
    id: f
    input: in-t
    output: out-t
    configuration:
      fail-values: ["flaky"]
      times: 2
"""

    async def main():
        records, _ = await run_app(pipeline, [("in-t", "flaky", None)], "out-t", 1)
        assert records[0].value == "flaky"

    run(main())


def test_errors_dead_letter(run):
    pipeline = """
id: p
topics:
  - name: in-t
    creation-mode: create-if-not-exists
  - name: out-t
    creation-mode: create-if-not-exists
errors:
  on-failure: dead-letter
  retries: 0
pipeline:
  - type: bad-record
    id: br
    input: in-t
    output: out-t
    configuration:
      bad-values: ["poison"]
"""

    async def main():
        app = make_app(pipeline)
        runner = LocalApplicationRunner("app", app)
        await runner.run()
        await runner.produce("in-t", "ok")
        await runner.produce("in-t", "poison")
        good = await runner.consume("out-t", 1)
        assert good[0].value == "ok"
        dead = await runner.consume("in-t-deadletter", 1)
        assert dead[0].value == "poison"
        from langstream_tpu.api.record import header_value

        assert "bad record" in header_value(dead[0], "error-msg")
        await runner.stop()

    run(main())


def test_errors_fail_crashes_application(run):
    pipeline = """
id: p
topics:
  - name: in-t
    creation-mode: create-if-not-exists
  - name: out-t
    creation-mode: create-if-not-exists
errors:
  on-failure: fail
  retries: 0
pipeline:
  - type: bad-record
    id: br
    input: in-t
    output: out-t
    configuration:
      bad-values: ["poison"]
"""

    async def main():
        app = make_app(pipeline)
        runner = LocalApplicationRunner("app", app)
        await runner.run()
        await runner.produce("in-t", "poison")
        await asyncio.sleep(0.3)
        with pytest.raises(RuntimeError, match="application failed"):
            await runner.stop(drain=False)

    run(main())


def test_parallelism_replicas(run):
    pipeline = """
id: p
topics:
  - name: in-t
    creation-mode: create-if-not-exists
    partitions: 2
  - name: out-t
    creation-mode: create-if-not-exists
pipeline:
  - type: upper
    id: up
    input: in-t
    output: out-t
    resources:
      parallelism: 2
"""

    async def main():
        app = make_app(pipeline)
        runner = LocalApplicationRunner("app", app)
        await runner.run()
        assert len(runner.runners) == 2
        for i in range(10):
            await runner.produce("in-t", f"v{i}", key=f"k{i}")
        records = await runner.consume("out-t", 10)
        assert len(records) == 10
        # both replicas got work (keys spread over 2 partitions)
        per_replica = [r._records_in for r in runner.runners]
        assert all(n > 0 for n in per_replica), per_replica
        await runner.stop()

    run(main())


def test_source_to_sink_agents(run):
    pipeline = """
id: p
pipeline:
  - type: list-source
    id: src
    configuration:
      items: ["a", "b"]
  - type: upper
    id: up
  - type: collect-sink
    id: snk
"""

    async def main():
        app = make_app(pipeline)
        runner = LocalApplicationRunner("app", app)
        await runner.run()
        await runner.wait_for_records_out("src", 2)
        await runner.stop()
        # locate the collect sink instance
        collected = []
        for r in runner.runners:
            if r.sink is not None and hasattr(r.sink, "collected"):
                collected = r.sink.collected
        assert sorted(x.value for x in collected) == ["A", "B"]

    run(main())


def test_metrics_info_http_server(run):
    """/metrics (prometheus) + /info (agent status) server
    (reference AgentRunner Jetty on :8080)."""
    import aiohttp

    from langstream_tpu.core.parser import ModelBuilder
    from langstream_tpu.runtime.local_runner import LocalApplicationRunner

    pipeline = (
        "module: default\nid: p\nname: m\ntopics:\n"
        "  - name: input-topic\n  - name: output-topic\n"
        "pipeline:\n  - name: echo\n    type: identity\n"
        "    input: input-topic\n    output: output-topic\n"
    )
    instance = "instance:\n  streamingCluster: {type: memory}\n  computeCluster: {type: local}\n"

    async def scenario():
        pkg = ModelBuilder.build_application_from_files(
            {"pipeline.yaml": pipeline}, instance, None
        )
        runner = LocalApplicationRunner("metrics-test", pkg.application)
        await runner.deploy()
        await runner.start()
        server = await runner.serve_metrics()
        try:
            await runner.produce("input-topic", "x")
            await runner.consume("output-topic", n=1, timeout=10)
            async with aiohttp.ClientSession() as session:
                async with session.get(f"{server.url}/metrics") as resp:
                    assert resp.status == 200
                    body = await resp.text()
                    assert "# TYPE" in body
                async with session.get(f"{server.url}/info") as resp:
                    info = await resp.json()
                    assert info and info[0]["agent-id"]
        finally:
            await server.stop()
            await runner.stop()

    run(scenario())


class GatedProcessor(SingleRecordProcessor):
    """Parks records whose value starts with "slow" until released; records
    the order in which processing STARTS (pipelining observability)."""

    gate = None  # asyncio.Event, installed by the test
    started: list = []
    hint = None  # what `inflight_records` says, installed by the test

    def inflight_records(self):
        return GatedProcessor.hint

    async def process_record(self, record: Record) -> list[Record]:
        GatedProcessor.started.append(str(record.value))
        if str(record.value).startswith("slow") and GatedProcessor.gate is not None:
            await GatedProcessor.gate.wait()
        return [record]


REGISTRY.register_agent(
    AgentTypeInfo(
        type="gated",
        component_type=ComponentType.PROCESSOR,
        factory=GatedProcessor,
        composable=False,
        config_model=ConfigModel(type="gated", allow_unknown=True),
    )
)


def test_pipelined_read_no_batch_head_of_line(run):
    """A slow record in batch k must not stop batch k+1 from STARTING
    (reference AgentRunner.java:669-729 keeps polling while processing
    completes asynchronously); results still land in source order."""
    pipeline = """
module: default
id: app
topics:
  - name: in-t
  - name: out-t
pipeline:
  - name: g
    type: gated
    input: in-t
    output: out-t
"""

    async def main():
        GatedProcessor.gate = asyncio.Event()
        GatedProcessor.started = []
        app = make_app(pipeline)
        runner = LocalApplicationRunner("test-app", app)
        await runner.run()
        try:
            # batch 1 = the slow record (first read returns just it);
            # batch 2 arrives while batch 1 is parked on the gate
            await runner.produce("in-t", "slow-1")
            for _ in range(50):
                if "slow-1" in GatedProcessor.started:
                    break
                await asyncio.sleep(0.02)
            await runner.produce("in-t", "fast-2")
            # pipelining: fast-2's processing STARTS while slow-1 is parked
            for _ in range(100):
                if "fast-2" in GatedProcessor.started:
                    break
                await asyncio.sleep(0.02)
            assert "fast-2" in GatedProcessor.started, (
                "batch 2 never started while batch 1 was in flight "
                "(head-of-line blocking is back)"
            )
            # nothing written yet: results are handled in source order
            GatedProcessor.gate.set()
            records = await runner.consume("out-t", 2, timeout=10)
            assert [str(r.value) for r in records] == ["slow-1", "fast-2"]
        finally:
            await runner.stop()

    run(main())


TRICKLE_PIPELINE = """
module: default
id: app
topics:
  - name: in-t
  - name: out-t
pipeline:
  - name: g
    type: gated
    input: in-t
    output: out-t
"""


async def _trickle(runner, names, started_at_least):
    """Produce `names` one by one, each once the one before has started (so
    every read returns a batch of ONE), as far as processing starts."""
    produced = 0
    for name in names:
        await runner.produce("in-t", name)
        produced += 1
        for _ in range(50):
            if name in GatedProcessor.started:
                break
            await asyncio.sleep(0.02)
        if name not in GatedProcessor.started:
            break
    assert len(GatedProcessor.started) >= started_at_least, GatedProcessor.started
    return list(GatedProcessor.started), produced


@pytest.mark.parametrize(
    "hint,started", [(None, 6), (12, 12), (3, 3)],
    ids=["batches-by-default", "twelve-records", "three-records"],
)
def test_inflight_bound_in_batches_or_in_records(run, hint, started):
    """Records that trickle in come as batches of one. By default the bound
    counts BATCHES (one with the writer, four queued, one waiting to be
    queued: six slow records start, whatever the step could take); where the
    step says how many RECORDS to keep in flight (`inflight_records`), a
    dozen all start, and a bound of three holds the fourth back until a
    result is written. Results land in source order either way."""
    names = [f"slow-{i}" for i in range(12)]

    async def main():
        GatedProcessor.gate = asyncio.Event()
        GatedProcessor.started = []
        GatedProcessor.hint = hint
        app = make_app(TRICKLE_PIPELINE)
        runner = LocalApplicationRunner("test-app", app)
        await runner.run()
        try:
            seen, produced = await _trickle(runner, names, started)
            assert seen == names[:started]
            await asyncio.sleep(0.2)
            assert GatedProcessor.started == names[:started]  # the bound holds
            GatedProcessor.gate.set()
            for name in names[produced:]:
                await runner.produce("in-t", name)
            records = await runner.consume("out-t", 12, timeout=10)
            assert [str(r.value) for r in records] == names
        finally:
            GatedProcessor.hint = None
            await runner.stop()

    run(main())


def test_a_step_s_service_says_how_many_records_to_keep_in_flight():
    """The hint's way up: the `tpu-serving` resource's ``inflight-records`` →
    its provider → the completions step → the GenAI agent → a fused chain."""
    from langstream_tpu.agents.genai.agent import GenAIToolKitAgent
    from langstream_tpu.agents.genai.steps import Step
    from langstream_tpu.ai.tpu_serving import TpuServingProvider
    from langstream_tpu.runtime.composite import CompositeAgentProcessor

    assert TpuServingProvider({"model": "tiny-test"}).inflight_records is None
    assert TpuServingProvider({"model": "tiny-test", "inflight-records": 160}).inflight_records == 160

    class Hinting(Step):
        def __init__(self, n):
            super().__init__({})
            self.n = n

        async def process(self, record, context):
            pass

        def inflight_records(self):
            return self.n

    agent, plain = GenAIToolKitAgent("compute"), GenAIToolKitAgent("compute")
    agent.steps, plain.steps = [Hinting(None), Hinting(160), Hinting(32)], [Hinting(None)]
    assert agent.inflight_records() == 160 and plain.inflight_records() is None
    assert CompositeAgentProcessor([plain, agent]).inflight_records() == 160
    assert CompositeAgentProcessor([plain]).inflight_records() is None

    # the completions step asks its provider when it starts
    import types

    from langstream_tpu.agents.genai.completions import ChatCompletionsStep

    class Anything:
        def __getattr__(self, _):
            return lambda *a, **k: Anything()

    provider = TpuServingProvider({"model": "tiny-test", "inflight-records": 160})
    context = types.SimpleNamespace(
        get_service_provider_registry=lambda: types.SimpleNamespace(get_provider=lambda _: provider),
        get_metrics_reporter=Anything, get_global_agent_id=lambda: "chat",
    )
    step = ChatCompletionsStep({"ai-service": "tpu", "model": "tiny-test"})
    assert step.inflight_records() is None
    asyncio.run(step.start(context))
    assert step.inflight_records() == 160

