"""Seeded open-loop load generator: one general generator, driven by the
workload file's parameters.

Copied from ``narwhal_tpu/node/benchmark_client.py`` (upstream
``benchmark_client.rs``): ``rate`` tx/s in 20 bursts a second over one
connection, the first transaction of each burst a sample (byte0 = 0, u64
id), the rest filler (byte0 = 1, u64 from the seeded stream), all
zero-padded to ``size``.  Different here: filler comes from ``--seed``,
the burst schedule never shifts (burst k is DUE at start + k/20 s
whatever happened before it), and every sample is written to ``--out``
as ``<id> <due wall time> <actual send wall time>``, so that latency is
taken from when a transaction was due and the generator's lateness is a
number and not a guess.

Every seed sends the same sizes at the same offsets; only bytes differ.
"""

from __future__ import annotations

import argparse
import asyncio
import struct
import sys
import time

import numpy as np

PRECISION = 20  # bursts per second
BURST_DURATION = 1.0 / PRECISION
WRITE_BUFFER = 8 * 1024 * 1024

ARRIVALS = ("steady",)


def sample_id(client: int, k: int) -> int:
    """Sample ids are disjoint between clients."""
    return (client << 32) + k


def sample_tx(client: int, k: int, size: int) -> bytes:
    """The bytes of client ``client``'s k-th sample transaction."""
    return b"\x00" + sample_id(client, k).to_bytes(8, "little") + bytes(size - 9)


async def wait_for(host: str, port: int) -> None:
    while True:
        try:
            _, w = await asyncio.open_connection(host, port)
            w.close()
            return
        except OSError:
            await asyncio.sleep(0.1)


async def send_load(target: str, size: int, rate: int, client: int,
                    seed: int, out_path: str) -> None:
    if size < 9:
        raise ValueError("transaction size must be at least 9 bytes")
    burst = max(1, rate // PRECISION)
    host, port = target.rsplit(":", 1)
    await wait_for(host, int(port))
    _, writer = await asyncio.open_connection(host, int(port), limit=WRITE_BUFFER)
    writer.transport.set_write_buffer_limits(high=WRITE_BUFFER)

    # One pre-framed buffer per burst, patched in place:
    # [u32 len][flag][u64][pad] per transaction.
    stride = 4 + size
    template = bytearray(
        struct.pack("<I", size) + b"\x01" + bytes(8) + bytes(size - 9)
    ) * burst
    template[4] = 0  # transaction 0 of every burst is the sample
    buf = np.frombuffer(template, dtype=np.uint8)
    u64_pos = (
        np.arange(burst)[:, None] * stride + 5 + np.arange(8)[None, :]
    ).ravel()
    filler_pos = u64_pos[8:]
    rng = np.random.default_rng([seed, client])

    loop = asyncio.get_running_loop()
    start_loop = loop.time()
    start_wall = time.time()
    print(f"start {start_wall:.6f} rate {rate} burst {burst} size {size}",
          flush=True)
    k = 0
    with open(out_path, "w", buffering=1) as out:
        while True:
            due = start_loop + k * BURST_DURATION
            now = loop.time()
            if now < due:
                await asyncio.sleep(due - now)
            template[5:13] = sample_id(client, k).to_bytes(8, "little")
            if burst > 1:
                buf[filler_pos] = rng.integers(
                    0, 256, size=filler_pos.size, dtype=np.uint8
                )
            sent = loop.time()
            try:
                writer.write(bytes(template))
                await writer.drain()
            except OSError:
                # The worker went away first: a normal end for an open loop.
                print("worker connection closed; stopping", flush=True)
                return
            out.write(
                f"{sample_id(client, k)} {start_wall + k * BURST_DURATION:.6f} "
                f"{start_wall + (sent - start_loop):.6f}\n"
            )
            k += 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("target", help="ip:port of the worker's transactions socket")
    p.add_argument("--size", type=int, required=True)
    p.add_argument("--rate", type=int, required=True)
    p.add_argument("--arrival", choices=ARRIVALS, default="steady")
    p.add_argument("--client", type=int, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    try:
        asyncio.run(send_load(args.target, args.size, args.rate, args.client,
                              args.seed, args.out))
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
