"""Full-system end-to-end tests: committees (primary + worker + consensus
each) in one process over loopback TCP; client transactions must come out as
committed certificates carrying their batch digest at every node (the
reference's `fab local` path as a test, SURVEY.md §7), including at N=10,
with multiple workers, under a crash fault, and across a node restart."""

import asyncio

import pytest

from narwhal_tpu.config import Parameters
from narwhal_tpu.network.framing import parse_address, write_frame
from narwhal_tpu.node import spawn_primary_node, spawn_worker_node
from tests.common import committee, keys


@pytest.fixture
def run():
    def _run(coro):
        return asyncio.run(asyncio.wait_for(coro, 60))

    return _run


def test_four_node_commit(run):
    async def go():
        c = committee(base_port=14000)
        params = Parameters(
            header_size=32,  # propose as soon as one digest arrives
            max_header_delay=100,
            batch_size=400,
            max_batch_delay=100,
        )
        commits = {i: [] for i in range(4)}
        nodes = []
        for i, kp in enumerate(keys()):
            nodes.append(
                await spawn_primary_node(
                    kp,
                    c,
                    params,
                    on_commit=lambda cert, i=i: commits[i].append(cert),
                )
            )
            nodes.append(await spawn_worker_node(kp, 0, c, params))

        # Push transactions into node 0's worker.
        host, port = parse_address(c.worker(keys()[0].name, 0).transactions)
        _, w = await asyncio.open_connection(host, port)
        txs = [bytes([1]) + i.to_bytes(8, "little") + bytes(91) for i in range(8)]
        for tx in txs:
            await write_frame(w, tx)

        # batch_size=400 seals every 4 of our 100 B txs into one batch; wait
        # until BOTH batches commit at every node.
        from narwhal_tpu.crypto import digest32
        from narwhal_tpu.messages import encode_batch

        expected = {
            digest32(encode_batch(txs[:4])),
            digest32(encode_batch(txs[4:])),
        }

        def payload_committed(certs):
            return expected <= {
                d for cert in certs for d in cert.header.payload
            }

        for _ in range(600):
            if all(payload_committed(v) for v in commits.values()):
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError(
                f"payload never committed: {[len(v) for v in commits.values()]}"
            )

        # All nodes commit the same certificates in the same order.
        seqs = [
            [cert.digest() for cert in commits[i]] for i in range(4)
        ]
        common = min(len(s) for s in seqs)
        assert common > 0
        for i in range(1, 4):
            assert seqs[i][:common] == seqs[0][:common]


        w.close()
        for node in nodes:
            await node.shutdown()

    run(go())


def test_multi_worker_commit(run):
    """Horizontal payload sharding (reference config/src/lib.rs:230-246):
    4 nodes × 2 workers; clients feed BOTH workers of node 0, and batches
    sealed by each worker id must be committed — proving the per-worker-id
    broadcast planes, digest‖worker_id payload keying, and the primary's
    payload bookkeeping work end to end."""

    async def go():
        c = committee(base_port=14200, workers=2)
        params = Parameters(
            header_size=32,
            max_header_delay=100,
            batch_size=400,
            max_batch_delay=100,
        )
        commits = {i: [] for i in range(4)}
        nodes = []
        for i, kp in enumerate(keys()):
            nodes.append(
                await spawn_primary_node(
                    kp,
                    c,
                    params,
                    on_commit=lambda cert, i=i: commits[i].append(cert),
                )
            )
            for wid in (0, 1):
                nodes.append(await spawn_worker_node(kp, wid, c, params))

        from narwhal_tpu.crypto import digest32
        from narwhal_tpu.messages import encode_batch

        expected = {}  # digest -> worker id that must have sealed it
        writers = []
        for wid in (0, 1):
            host, port = parse_address(
                c.worker(keys()[0].name, wid).transactions
            )
            _, w = await asyncio.open_connection(host, port)
            writers.append(w)
            txs = [
                bytes([1]) + (wid * 100 + i).to_bytes(8, "little") + bytes(91)
                for i in range(4)
            ]
            for tx in txs:
                await write_frame(w, tx)
            expected[digest32(encode_batch(txs))] = wid

        def committed_payload(certs):
            return {
                d: wid
                for cert in certs
                for d, wid in cert.header.payload.items()
            }

        for _ in range(600):
            if all(
                set(expected) <= set(committed_payload(v))
                for v in commits.values()
            ):
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError(
                "multi-worker payload never committed: "
                f"{[len(v) for v in commits.values()]}"
            )

        # Every committed digest is attributed to the worker that sealed it.
        for i in range(4):
            payload = committed_payload(commits[i])
            for d, wid in expected.items():
                assert payload[d] == wid, (i, payload[d], wid)

        for w in writers:
            w.close()
        for node in nodes:
            await node.shutdown()

    run(go())


def test_restarted_node_rejoins_and_commits(run, tmp_path):
    """Crash-stop recovery (reference §5: persisted batches/headers/certs +
    ReliableSender retransmission + waiter sync): node 3 is shut down after
    the first commit and restarted from its on-disk stores; it must rejoin
    the committee — catching up its round via incoming certificates — and
    commit new transactions."""

    async def go():
        c = committee(base_port=14800)
        params = Parameters(
            header_size=32,
            max_header_delay=100,
            batch_size=400,
            max_batch_delay=100,
        )
        kps = keys()
        commits = {i: [] for i in range(4)}

        async def boot(i, kp):
            primary = await spawn_primary_node(
                kp,
                c,
                params,
                store_path=f"{tmp_path}/primary-{i}/store.log",
                on_commit=lambda cert, i=i: commits[i].append(cert),
            )
            worker = await spawn_worker_node(
                kp, 0, c, params, store_path=f"{tmp_path}/worker-{i}/store.log"
            )
            return [primary, worker]

        nodes = {i: await boot(i, kp) for i, kp in enumerate(kps)}

        from narwhal_tpu.crypto import digest32
        from narwhal_tpu.messages import encode_batch

        host, port = parse_address(c.worker(kps[0].name, 0).transactions)

        async def push(txs):
            _, w = await asyncio.open_connection(host, port)
            for tx in txs:
                await write_frame(w, tx)
            w.close()

        # Combined budget of BOTH waits stays under the run fixture's 60 s
        # wait_for, so failures raise the diagnostic AssertionError (not a
        # bare TimeoutError) and the nodes still shut down.
        async def committed_everywhere(digest, who):
            for _ in range(250):
                if all(
                    digest in {d for cert in commits[i] for d in cert.header.payload}
                    for i in who
                ):
                    return True
                await asyncio.sleep(0.1)
            return False

        txs1 = [bytes([1]) + i.to_bytes(8, "little") + bytes(91) for i in range(4)]
        await push(txs1)
        assert await committed_everywhere(
            digest32(encode_batch(txs1)), range(4)
        ), "first batch never committed"

        # Crash node 3 and restart it from its persisted stores.  The
        # consensus frontier checkpoint must already be on disk — that is
        # what the reboot below restores.  The checkpoint rewrite runs in
        # an executor AFTER the commit is delivered downstream (which is
        # what committed_everywhere observed), so on a starved host the
        # file can trail the commit by a beat — wait for it BEFORE the
        # crash rather than racing the shutdown's task cancellation.
        import os as _os

        ckpt = f"{tmp_path}/primary-3/store.log.consensus.ckpt"
        for _ in range(100):
            if _os.path.exists(ckpt):
                break
            await asyncio.sleep(0.1)
        for node in nodes[3]:
            await node.shutdown()

        assert _os.path.exists(
            ckpt
        ), "consensus checkpoint never written before the crash"
        nodes[3] = await boot(3, kps[3])

        txs2 = [bytes([2]) + i.to_bytes(8, "little") + bytes(91) for i in range(4)]
        await push(txs2)
        # The restarted node must catch up — its consensus frontier is
        # RESTORED from the checkpoint (beyond reference parity: the
        # reference leaves consensus state unpersisted,
        # consensus/src/lib.rs:18-19, and re-delivers history) — and
        # commit the new batch.
        assert await committed_everywhere(
            digest32(encode_batch(txs2)), range(4)
        ), (
            "post-restart batch never committed: "
            f"{[len(commits[i]) for i in range(4)]}"
        )
        # No double delivery across the restart — a regression guard (in
        # this healthy-peer scenario the persisted store already keeps
        # history out of consensus; the checkpoint's dedupe is
        # demonstrated directly against a catch-up replay in
        # test_consensus.py::test_checkpoint_restore_resumes_without_redelivery).
        delivered = [bytes(cert.digest()) for cert in commits[3]]
        assert len(delivered) == len(set(delivered)), (
            "restarted node re-delivered committed certificates"
        )

        for pair in nodes.values():
            for node in pair:
                await node.shutdown()

    run(go())


def test_ten_node_commit(run):
    """N=10 committee (quorum 7): the protocol must drive rounds and commit
    at a committee size where the 4-node fixtures hide nothing — larger
    vote aggregation, wider broadcast fan-out, bigger parent sets
    (BASELINE.json names 10/20/50-node configs; VERDICT r4 flagged that
    nothing ever ran above N=4)."""

    async def go():
        n = 10
        c = committee(base_port=14600, n=n)
        params = Parameters(
            header_size=32,
            max_header_delay=200,
            batch_size=400,
            max_batch_delay=100,
        )
        commits = {i: [] for i in range(n)}
        nodes = []
        for i, kp in enumerate(keys(n)):
            nodes.append(
                await spawn_primary_node(
                    kp,
                    c,
                    params,
                    on_commit=lambda cert, i=i: commits[i].append(cert),
                )
            )
            nodes.append(await spawn_worker_node(kp, 0, c, params))

        host, port = parse_address(c.worker(keys(n)[0].name, 0).transactions)
        _, w = await asyncio.open_connection(host, port)
        txs = [bytes([1]) + i.to_bytes(8, "little") + bytes(91) for i in range(4)]
        for tx in txs:
            await write_frame(w, tx)

        from narwhal_tpu.crypto import digest32
        from narwhal_tpu.messages import encode_batch

        expected = digest32(encode_batch(txs))

        def payload_committed(certs):
            return expected in {
                d for cert in certs for d in cert.header.payload
            }

        # Poll budget < the run fixture's 60 s wait_for, so on failure the
        # diagnostic AssertionError (not a bare TimeoutError) fires and the
        # nodes still shut down.
        for _ in range(400):
            if all(payload_committed(v) for v in commits.values()):
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError(
                "payload never committed at N=10: "
                f"{[len(v) for v in commits.values()]}"
            )

        # All ten nodes agree on the commit order.
        seqs = [[cert.digest() for cert in commits[i]] for i in range(n)]
        common = min(len(s) for s in seqs)
        assert common > 0
        for i in range(1, n):
            assert seqs[i][:common] == seqs[0][:common]

        w.close()
        for node in nodes:
            await node.shutdown()

    run(go())


def test_commit_with_crash_fault(run):
    """f=1 crash fault: the last node never boots (the reference's fault
    injection, benchmark/local.py:77); the 3 live nodes (2f+1 stake) must
    still drive rounds and commit client transactions."""

    async def go():
        c = committee(base_port=14400)
        params = Parameters(
            header_size=32,
            max_header_delay=100,
            batch_size=400,
            max_batch_delay=100,
        )
        live = keys()[:3]  # node 3 is crashed from the start
        commits = {i: [] for i in range(3)}
        nodes = []
        for i, kp in enumerate(live):
            nodes.append(
                await spawn_primary_node(
                    kp,
                    c,
                    params,
                    on_commit=lambda cert, i=i: commits[i].append(cert),
                )
            )
            nodes.append(await spawn_worker_node(kp, 0, c, params))

        host, port = parse_address(c.worker(live[0].name, 0).transactions)
        _, w = await asyncio.open_connection(host, port)
        txs = [bytes([1]) + i.to_bytes(8, "little") + bytes(91) for i in range(4)]
        for tx in txs:
            await write_frame(w, tx)

        from narwhal_tpu.crypto import digest32
        from narwhal_tpu.messages import encode_batch

        expected = digest32(encode_batch(txs))

        def payload_committed(certs):
            return expected in {
                d for cert in certs for d in cert.header.payload
            }

        for _ in range(600):
            if all(payload_committed(v) for v in commits.values()):
                break
            await asyncio.sleep(0.1)
        else:
            raise AssertionError(
                "payload never committed under f=1: "
                f"{[len(v) for v in commits.values()]}"
            )

        # The live nodes agree on the commit order.
        seqs = [[cert.digest() for cert in commits[i]] for i in range(3)]
        common = min(len(s) for s in seqs)
        assert common > 0
        for i in range(1, 3):
            assert seqs[i][:common] == seqs[0][:common]

        w.close()
        for node in nodes:
            await node.shutdown()

    run(go())


def test_prewarm_cli_needs_a_backend(capsys):
    """There is nothing to warm without one: no `--crypto-backend`, no run."""
    from narwhal_tpu.node.main import main as node_main

    with pytest.raises(SystemExit) as refused:
        node_main(["prewarm"])
    assert refused.value.code == 2
    assert "--crypto-backend" in capsys.readouterr().err


def test_prewarm_cli(tmp_path, monkeypatch):
    """`node prewarm --crypto-backend jax` builds the verify kernel for
    every rung of the pad ladder and exits 0 — the step the bench harness
    runs before spawning SEVERAL device-backed nodes so their boot warmup
    is a load of the program files it wrote.
    Runs on the CPU jax backend here, on the tests' one-rung ladder."""
    from narwhal_tpu.node.main import main as node_main
    from narwhal_tpu.ops import programs

    # The program file goes to this test's directory, not the checkout's.
    monkeypatch.setattr(programs, "program_dir", lambda: str(tmp_path / "programs"))

    from narwhal_tpu.crypto import backend as crypto_backend

    try:
        rc = node_main(["prewarm", "--crypto-backend", "jax"])
    finally:
        # prewarm selects the jax backend process-globally; put the
        # default back so later tests in this session see cpu.
        crypto_backend.set_backend("cpu")
    assert rc == 0
