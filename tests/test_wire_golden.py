"""Golden wire-format tests: byte layouts HAND-ASSEMBLED here from the
published protocol specifications, asserted byte-identical against the
codecs.

Why this exists: the Kafka/Pulsar/CQL clients have only
ever been exercised against fakes written by the same hand, so a shared
misreading of a wire format would pass every integration test. These tests
break that loop as far as a no-egress image allows: the EXPECTED bytes are
laid out field-by-field with struct.pack from the public specs (Kafka
record-batch v2 + request header, Pulsar framing + protobuf command
encoding, CQL v4 frame header + notation types), not produced by the codec
under test. An accidental codec change that drifts off the spec layout now
fails loudly with a byte diff.

What this is NOT: a capture from a real broker. The remaining rung —
replaying transcripts recorded off real Kafka/Pulsar/Cassandra servers —
needs network egress; docs/COMPAT_RUNBOOK.md documents exactly how to
capture and vendor those when a real broker is reachable.

Spec sources (public):
- Kafka protocol guide (kafka.apache.org/protocol) — request header v1,
  record batch v2 ("magic 2") layout, CRC32C over attributes..end.
- Pulsar binary protocol (pulsar.apache.org/docs/developing-binary-protocol)
  — [totalSize][commandSize][command] simple frames, [magic 0x0e01][crc32c]
  payload frames, protobuf BaseCommand.
- CQL binary protocol v4 spec (native_protocol_v4.spec in cassandra.git) —
  frame header, STARTUP string map, notation encodings.
- RFC 3720 CRC32C test vector (already pinned in test_pulsar).
"""

import struct

from langstream_tpu.agents.vector import cql_protocol as cql
from langstream_tpu.messaging import kafka_protocol as kp
from langstream_tpu.messaging import pulsar_protocol as pp

# ---------------------------------------------------------------------------
# Kafka
# ---------------------------------------------------------------------------


def test_kafka_request_header_layout():
    """Request header v1: apiKey int16, apiVersion int16, correlationId
    int32, clientId nullable-string (int16 len + bytes)."""
    payload = b"\x01\x02\x03"
    got = kp.encode_request(3, 7, "ls", payload)  # 3 = Metadata
    version = kp.API_VERSIONS[3]
    expect_frame = (
        struct.pack(">hhih", 3, version, 7, 2) + b"ls" + payload
    )
    expect = struct.pack(">i", len(expect_frame)) + expect_frame
    assert got == expect


def test_kafka_record_batch_v2_spec_layout():
    """Hand-assemble a one-record batch exactly as the spec lays it out and
    require byte identity from the encoder."""
    key, value = b"k1", b"hello"
    ts = 1_700_000_000_123

    # record (its own length-prefixed blob): attributes int8=0,
    # timestampDelta varlong=0, offsetDelta varint=0, key len+bytes,
    # value len+bytes, headers count varint=1 with ("h", b"v")
    record = (
        b"\x00"  # attributes
        + b"\x00"  # timestampDelta zigzag(0)
        + b"\x00"  # offsetDelta zigzag(0)
        + b"\x04" + key  # zigzag(2)=4
        + b"\x0a" + value  # zigzag(5)=10
        + b"\x02"  # headerCount zigzag(1)=2
        + b"\x02h"  # header key len zigzag(1)=2, "h"
        + b"\x02v"  # header value len zigzag(1)=2, "v"
    )
    assert len(record) < 64
    records_blob = bytes([len(record) * 2]) + record  # varint length prefix

    # batch body covered by the CRC: attributes int16=0, lastOffsetDelta
    # int32=0, baseTimestamp int64, maxTimestamp int64, producerId -1,
    # producerEpoch -1, baseSequence -1, recordCount 1, records
    body = (
        struct.pack(">hiqqqhii", 0, 0, ts, ts, -1, -1, -1, 1) + records_blob
    )
    expect = (
        struct.pack(">qi", 0, 4 + 1 + 4 + len(body))  # baseOffset, batchLength
        + struct.pack(">i", -1)  # partitionLeaderEpoch
        + b"\x02"  # magic = 2
        + struct.pack(">I", pp.crc32c(body))  # CRC32C (RFC-vector-pinned impl)
        + body
    )
    got = kp.encode_record_batch(
        [kp.WireRecord(key=key, value=value, headers=[("h", b"v")], timestamp_ms=ts)]
    )
    assert got == expect

    # and the decoder round-trips the hand-made bytes
    [back] = kp.decode_record_batches(expect)
    assert (back.key, back.value, back.headers, back.timestamp_ms) == (
        key, value, [("h", b"v")], ts
    )


def test_kafka_murmur2_reference_algorithm():
    """murmur2 re-implemented here from the published Kafka algorithm
    (seed 0x9747b28c ^ len, M=0x5bd1e995, R=24, final x^=x>>>13, *=M,
    x^=x>>>15) — guards the codec impl against drift."""

    def ref_murmur2(data: bytes) -> int:
        m, r = 0x5BD1E995, 24
        mask = 0xFFFFFFFF
        h = (0x9747B28C ^ len(data)) & mask
        n4 = len(data) // 4
        for i in range(n4):
            k = int.from_bytes(data[i * 4 : i * 4 + 4], "little", signed=False)
            k = (k * m) & mask
            k ^= k >> r
            k = (k * m) & mask
            h = (h * m) & mask
            h ^= k
        tail = data[n4 * 4 :]
        if len(tail) == 3:
            h ^= tail[2] << 16
        if len(tail) >= 2:
            h ^= tail[1] << 8
        if len(tail) >= 1:
            h ^= tail[0]
            h = (h * m) & mask
        h ^= h >> 13
        h = (h * m) & mask
        h ^= h >> 15
        # Kafka interprets the result as a signed int32
        return h - (1 << 32) if h >= (1 << 31) else h

    for key in (b"", b"a", b"ab", b"abc", b"abcd", b"key-42", b"\x00\xff" * 9):
        # the codec returns the uint32 bit pattern; Java returns the same
        # bits as a signed int32 — identical through toPositive()
        assert kp.murmur2(key) == ref_murmur2(key) & 0xFFFFFFFF, key
    # partition routing masks the sign bit (toPositive in the Java client)
    for key in (b"a", b"key-42", b"\xfe\xed"):
        assert kp.murmur2_partition(key, 12) == (ref_murmur2(key) & 0x7FFFFFFF) % 12


# ---------------------------------------------------------------------------
# Pulsar
# ---------------------------------------------------------------------------


def _pb_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def test_pulsar_simple_frame_layout():
    """PING: BaseCommand{type=PING(18), ping={}} hand-encoded as protobuf
    (tag 1 varint 18; tag 18 length-delimited empty), framed as
    [totalSize][commandSize][command]."""
    cmd = (
        _pb_varint((1 << 3) | 0) + _pb_varint(18)  # type = PING
        + _pb_varint((18 << 3) | 2) + b"\x00"  # ping = {} (empty message)
    )
    expect = struct.pack(">II", 4 + len(cmd), len(cmd)) + cmd
    got = pp.frame(pp.encode_command("ping", {}))
    assert got == expect
    name, fields, metadata, payload = pp.split_frame(expect[4:])
    assert name == "ping" and metadata is None and payload == b""


def test_pulsar_payload_frame_layout():
    """SEND frame: [totalSize][cmdSize][cmd][0x0e01][crc32c][mdSize][md][payload],
    crc32c over [mdSize][md][payload]."""
    cmd = pp.encode_command(
        "send", {"producer_id": 1, "sequence_id": 5, "num_messages": 1}
    )
    md = pp.encode_message(
        pp.MESSAGE_METADATA,
        {"producer_name": "p", "sequence_id": 5, "publish_time": 1000,
         "uncompressed_size": 3},
    )
    payload = b"abc"
    checked = struct.pack(">I", len(md)) + md + payload
    rest = b"\x0e\x01" + struct.pack(">I", pp.crc32c(checked)) + checked
    expect = (
        struct.pack(">II", 4 + len(cmd) + len(rest), len(cmd)) + cmd + rest
    )
    assert pp.payload_frame(cmd, md, payload) == expect


def test_pulsar_metadata_protobuf_layout():
    """MessageMetadata fields land on the spec's field numbers with the
    spec's wire types (1 producer_name string, 2 sequence_id, 3
    publish_time, 6 partition_key)."""
    md = pp.encode_message(
        pp.MESSAGE_METADATA,
        {"producer_name": "p", "sequence_id": 5, "publish_time": 7,
         "partition_key": "k"},
    )
    expect = (
        bytes([(1 << 3) | 2]) + b"\x01p"
        + bytes([(2 << 3) | 0]) + b"\x05"
        + bytes([(3 << 3) | 0]) + b"\x07"
        + bytes([(6 << 3) | 2]) + b"\x01k"
    )
    assert md == expect


# ---------------------------------------------------------------------------
# CQL v4
# ---------------------------------------------------------------------------


def test_cql_frame_header_layout():
    """v4 header: version 0x04 (request), flags 0x00, stream int16, opcode,
    body length int32."""
    body = b"\x00\x00"
    got = cql.frame(cql.OP_OPTIONS, body, stream=3)
    expect = bytes([0x04, 0x00]) + struct.pack(">hB", 3, cql.OP_OPTIONS)
    expect += struct.pack(">I", len(body)) + body
    assert got == expect
    version, stream, opcode, length = cql.parse_header(got[:9])
    assert (version, stream, opcode, length) == (4, 3, cql.OP_OPTIONS, 2)


def test_cql_startup_body_is_spec_string_map():
    """STARTUP body: [string map] = count int16, then len-prefixed pairs;
    the required CQL_VERSION entry."""
    body = cql.startup_body()
    expect = (
        struct.pack(">h", 1)
        + struct.pack(">h", 11) + b"CQL_VERSION"
        + struct.pack(">h", 5) + b"3.0.0"
    )
    assert body == expect


def test_cql_value_encodings_match_notation():
    """[int] and [bigint] are big-endian fixed width; text is raw UTF-8;
    a list<int> value is count int32 + int32-length-prefixed elements."""
    assert cql.encode_value(cql.T_INT, 7) == struct.pack(">i", 7)
    assert cql.encode_value(cql.T_BIGINT, -2) == struct.pack(">q", -2)
    assert cql.encode_value(cql.T_VARCHAR, "hé") == "hé".encode()
    got = cql.encode_value(("list", cql.T_INT), [1, 2])
    expect = struct.pack(">i", 2) + struct.pack(">i", 4) + struct.pack(">i", 1)
    expect += struct.pack(">i", 4) + struct.pack(">i", 2)
    assert got == expect
    assert cql.decode_value(("list", cql.T_INT), got) == [1, 2]
