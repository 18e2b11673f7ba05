"""The full RAG app on-platform with zero external
calls: directory source → text extract → split → TPU embeddings → embedded
vector store; then question → embed → vector search → MMR re-rank → TPU
chat completion. (The shipped example uses webcrawler-source; this test
substitutes local-directory-source because tests have no egress.)"""

import json

from langstream_tpu.core.parser import ModelBuilder
from langstream_tpu.runtime.local_runner import LocalApplicationRunner

CONFIG = """
configuration:
  resources:
    - type: tpu-serving
      name: tpu
      configuration:
        model: tiny-test
        tokenizer: byte
        max-seq-len: 256
    - type: vector-database
      name: vdb
      id: vdb
      configuration:
        service: local-vector
"""

INGEST = """
module: default
id: ingest
name: ingest
topics:
  - name: chunks-topic
    creation-mode: create-if-not-exists
pipeline:
  - name: read
    type: local-directory-source
    configuration:
      directory: "{docs_dir}"
  - name: extract
    type: text-extractor
  - name: split
    type: text-splitter
    configuration:
      chunk_size: 120
      chunk_overlap: 20
  - name: to-structure
    type: document-to-json
    configuration:
      text-field: text
  - name: embed
    type: compute-ai-embeddings
    output: chunks-topic
    configuration:
      model: tiny-test
      text: "{{{{ value.text }}}}"
      embeddings-field: value.embeddings
      batch-size: 4
  - name: write
    type: vector-db-sink
    input: chunks-topic
    configuration:
      datasource: vdb
      index-name: docs
      id: "fn:uuid()"
      vector: value.embeddings
      fields:
        - name: text
          expression: value.text
"""

QUERY = """
module: default
id: query
name: query
topics:
  - name: rag-questions
    creation-mode: create-if-not-exists
  - name: rag-answers
    creation-mode: create-if-not-exists
pipeline:
  - name: to-structure
    type: document-to-json
    input: rag-questions
    configuration:
      text-field: question
  - name: embed-question
    type: compute-ai-embeddings
    configuration:
      model: tiny-test
      text: "{{ value.question }}"
      embeddings-field: value.embeddings
  - name: search
    type: query-vector-db
    configuration:
      datasource: vdb
      query: '{"index": "docs", "vector": "?", "topK": 5, "include-vectors": true}'
      fields:
        - value.embeddings
      output-field: value.related
  - name: rerank
    type: re-rank
    configuration:
      field: value.related
      output-field: value.context
      query-embeddings: value.embeddings
      embeddings-field: record.vector
      text-field: record.text
      algorithm: MMR
      output-mode: text
      max: 2
  - name: answer
    type: ai-chat-completions
    output: rag-answers
    configuration:
      model: tiny-test
      completion-field: value.answer
      max-new-tokens: 8
      messages:
        - role: system
          content: "Context: {{ value.context }}"
        - role: user
          content: "{{ value.question }}"
"""

INSTANCE = """
instance:
  streamingCluster:
    type: memory
  computeCluster:
    type: local
"""


def test_full_rag_on_platform(run, tmp_path):
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "tpus.txt").write_text(
        "TPUs are matrix accelerators. The MXU is a systolic array. "
        "HBM bandwidth is usually the bottleneck for decoding."
    )
    (docs / "brokers.txt").write_text(
        "Topics carry records between agents. Offsets commit in contiguous "
        "prefixes so redelivery preserves at-least-once semantics."
    )

    files = {
        "ingest.yaml": INGEST.format(docs_dir=docs),
        "query.yaml": QUERY,
        "configuration.yaml": CONFIG,
    }
    pkg = ModelBuilder.build_application_from_files(files, INSTANCE, None)

    async def scenario():
        runner = LocalApplicationRunner("rag", pkg.application)
        await runner.deploy()
        await runner.start()
        try:
            # wait for ingestion: chunks land in the vector store
            import asyncio

            ds = runner._service_registry.get_datasource("vdb")
            for _ in range(300):
                if ds.has_index("docs") and len(ds.search("docs", [1.0] + [0.0] * 63, 100)) >= 2:
                    break
                await asyncio.sleep(0.1)
            assert ds.has_index("docs"), "ingestion never wrote the index"

            await runner.produce("rag-questions", "what limits decoding speed?")
            out = await runner.consume("rag-answers", n=1, timeout=120)
            value = json.loads(out[0].value)
            assert "answer" in value and isinstance(value["answer"], str)
            # retrieval actually surfaced stored context
            assert value["context"]
        finally:
            await runner.stop()

    run(scenario())
