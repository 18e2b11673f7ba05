"""The application under test, written as files and built the way
`langstream-tpu run local` builds one: ModelBuilder →
LocalApplicationRunner → serve_gateway(). The shape is
examples/applications/tpu-completions (document-to-json, then
ai-chat-completions streaming to a topic) with one such pipeline per output
cap, because `max-tokens` is a key of the step; every step names the same
`tpu-serving` resource, and the local runner keeps one provider, so one
engine, per resource."""

from __future__ import annotations

from pathlib import Path

import yaml

RESOURCE_ID = "tpu"


def pipeline_file(model: str, cap: int) -> dict:
    return {
        "module": "default",
        "id": f"completions-{cap}",
        "name": f"completions capped at {cap} tokens",
        "topics": [
            {"name": f"{kind}-{cap}", "creation-mode": "create-if-not-exists"}
            for kind in ("questions", "answers", "debug")
        ],
        "pipeline": [
            {
                "name": f"convert-{cap}",
                "type": "document-to-json",
                "input": f"questions-{cap}",
                "configuration": {"text-field": "question"},
            },
            {
                "name": f"chat-{cap}",
                "type": "ai-chat-completions",
                "output": f"debug-{cap}",
                "configuration": {
                    "model": model,
                    "ai-service": RESOURCE_ID,
                    "stream-to-topic": f"answers-{cap}",
                    "stream-response-completion-field": "value",
                    "completion-field": "value.answer",
                    "log-field": "value.prompt",
                    "max-tokens": cap,
                    "messages": [{"role": "user", "content": "{{ value.question }}"}],
                },
            },
        ],
    }


def gateways_file(caps: list[int]) -> dict:
    session = {"key": "langstream-client-session-id", "value-from-parameters": "sessionId"}
    gateways = []
    for cap in caps:
        gateways += [
            {
                "id": f"chat-{cap}", "type": "chat", "parameters": ["sessionId"],
                "chat-options": {
                    "questions-topic": f"questions-{cap}",
                    "answers-topic": f"answers-{cap}",
                    "headers": [session],
                },
            },
            {"id": f"produce-{cap}", "type": "produce", "topic": f"questions-{cap}"},
            {"id": f"consume-{cap}", "type": "consume", "topic": f"answers-{cap}"},
        ]
    return {"gateways": gateways}


def write_app(root: Path, model: str, caps: list[int], serving: dict) -> tuple[Path, Path]:
    """`serving` is the whole tpu-serving resource configuration."""
    app = root / "app"
    app.mkdir()
    for cap in caps:
        (app / f"pipeline-{cap}.yaml").write_text(yaml.safe_dump(pipeline_file(model, cap)))
    (app / "gateways.yaml").write_text(yaml.safe_dump(gateways_file(caps)))
    (app / "configuration.yaml").write_text(yaml.safe_dump({
        "configuration": {"resources": [
            {"type": "tpu-serving", "name": RESOURCE_ID, "id": RESOURCE_ID,
             "configuration": serving},
        ]},
    }))
    instance = root / "instance.yaml"
    instance.write_text(yaml.safe_dump({
        "instance": {
            "streamingCluster": {"type": "memory"},
            "computeCluster": {"type": "local"},
        },
    }))
    return app, instance


def gateway_urls(ws_url: str, app_id: str, caps: list[int]) -> dict:
    return {
        str(cap): {
            kind: f"{ws_url}/v1/{kind}/default/{app_id}/{kind}-{cap}"
            for kind in ("chat", "produce", "consume")
        }
        for cap in caps
    }
