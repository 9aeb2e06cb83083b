"""Serving launcher: batched prefill + decode with on-device OnPair
detokenisation (the paper's decompression path in the serving loop).

Prompts can come from the CLI or straight out of the compressed corpus
store (``--doc-ids``): the corpus lives in memory compressed, and prompt
materialisation is a batched store multiget through the Pallas decoder.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --smoke \
      --prompts "the quick" "compression" --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --smoke \
      --doc-ids 3 17 4242 --max-new 8

``--shard-server`` flips the launcher into its other role: a per-shard RPC
server process for the multi-process serving tier (``repro.net``) — no LM
(heavy imports only happen on the LM path). Both roles keep JAX's
persistent compilation cache (:mod:`repro.kernels.cache`):

  PYTHONPATH=src python -m repro.launch.serve \
      --shard-server /data/corpus/shard-0002 --port 9102
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-780m")
    ap.add_argument("--shard-server", default=None, metavar="SHARD_DIR",
                    help="serve this shard directory (<dir>/shard-000k) over "
                         "TCP via repro.net.shard_server and exit when "
                         "interrupted; skips the LM entirely")
    ap.add_argument("--host", default="127.0.0.1",
                    help="--shard-server bind host")
    ap.add_argument("--port", type=int, default=0,
                    help="--shard-server bind port (0 = kernel-assigned)")
    ap.add_argument("--read-only", action="store_true",
                    help="--shard-server: serve as a read-only replica")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="also serve Prometheus /metrics + the /traces "
                         "slow-request dump on this port (0 = "
                         "kernel-assigned); applies to both roles")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompts", nargs="+",
                    default=["the quick brown", "in memory database"])
    ap.add_argument("--doc-ids", type=int, nargs="*", default=None,
                    help="additionally serve prompts fetched by id from the "
                         "OnPair-compressed corpus store (repro.store)")
    ap.add_argument("--store-dir", default=None,
                    help="open a persisted CompressedStringStore (built with "
                         "store.save(dir)) instead of compressing in-process; "
                         "the store's saved dictionary artifact becomes the "
                         "tokenizer vocabulary")
    ap.add_argument("--writable", action="store_true",
                    help="open --store-dir as a MutableStringStore (accepts "
                         "appends against the frozen dictionary; versioned "
                         "directory layout)")
    ap.add_argument("--append", nargs="*", default=None, metavar="DOC",
                    help="append these documents to the writable store "
                         "before serving (their new ids are also served as "
                         "prompts); prints the drift snapshot")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()
    from repro.kernels.cache import use_compile_cache
    use_compile_cache()

    if args.shard_server:
        # RPC-server role: the store alone — never pull in the LM
        from repro.net.shard_server import run
        run(args.shard_server, host=args.host, port=args.port,
            read_only=args.read_only, metrics_port=args.metrics_port)
        return

    if args.metrics_port is not None:
        # LM path: expose the store/client/kernel metrics this process
        # records while it serves (scrape http://host:port/metrics)
        from repro.obs import start_metrics_server
        metrics = start_metrics_server(port=args.metrics_port)
        print(f"metrics: http://127.0.0.1:{metrics.port}/metrics", flush=True)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.core.tokenizer import OnPairTokenizer
    from repro.data.synth import load_dataset
    from repro.models.model import build_params, serve_decode, serve_prefill

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()

    client = None
    if args.store_dir:
        # persisted-store path through the v3 client layer: the store URL
        # decides the backend (writable vs read-only here; a sharded dir or
        # a remote cluster would be the same call with another scheme), and
        # the saved dictionary artifact IS the vocab — nothing is retrained
        from repro.client import connect
        from repro.core import registry
        scheme = "mut" if args.writable else "file"
        client = connect(f"{scheme}://{args.store_dir}")
        codec = registry.resolve(client.backend.artifact.codec)
        if codec not in ("onpair", "onpair16"):
            raise SystemExit(
                f"--store-dir: store was built with codec {codec!r}; the LM "
                "tokenizer vocabulary is an OnPair dictionary — rebuild the "
                "store with codec='onpair16'")
        tok = OnPairTokenizer.from_artifact(client.backend.artifact)
    else:
        # OnPair tokenizer trained on a small corpus (vocab == dictionary)
        corpus_strings = load_dataset("book_titles", 1 << 20)
        tok = OnPairTokenizer.train(corpus_strings, sample_bytes=1 << 20)
    from dataclasses import replace
    cfg = replace(cfg, vocab_size=tok.vocab_size)
    params = build_params(cfg, seed=0)

    if args.append:
        # ingest path: parse new docs against the store's frozen dictionary
        if client is None or not args.writable:
            raise SystemExit("--append requires --store-dir with --writable")
        new_ids = client.extend([d.encode() for d in args.append])
        client.save()  # ingest is durable, not in-memory only
        drift = client.backend.drift.snapshot()
        print(f"appended {len(new_ids)} docs (ids {new_ids[0]}..{new_ids[-1]}), "
              f"tail {client.stats()['backend']['n_tail_strings']} strings, "
              f"saved to {args.store_dir}, drift {drift['drift']:.3f} "
              f"(compact recommended: {drift['should_compact']})")
        args.doc_ids = list(args.doc_ids or []) + new_ids

    prompt_bytes = [p.encode() for p in args.prompts]
    if args.doc_ids:
        # corpus path: the store answers the prompt fetch as one batched,
        # length-bucketed kernel decode over the compressed payload
        if client is None:
            from repro.client import wrap
            from repro.core.codec import Encoder
            from repro.store import CompressedStringStore
            artifact = tok.to_artifact()
            client = wrap(CompressedStringStore(
                artifact, Encoder(artifact).encode(corpus_strings)))
        docs = client.multiget(args.doc_ids)
        prompt_bytes += docs
        # display names only; latin-1 roundtrips arbitrary doc bytes
        args.prompts = list(args.prompts) + [d.decode("latin-1") for d in docs]
        snap = client.stats()["backend"]
        print(f"store: {snap['n_strings']} docs in {snap['n_segments']} "
              f"segments ({snap['backend']} backend), fetched "
              f"{len(docs)} prompts, jit shapes {snap['jit_shapes']}")

    ids = tok.encode_batch(prompt_bytes, bos=True)
    L = max(len(s) for s in ids)
    tokens = np.zeros((len(ids), L), np.int32)
    for i, s in enumerate(ids):
        tokens[i, : len(s)] = s

    t0 = time.perf_counter()
    logits, cache = serve_prefill(params, {"tokens": jnp.asarray(tokens)},
                                  cfg, max_seq=args.max_seq)
    print(f"prefill: {tokens.shape} in {time.perf_counter() - t0:.2f}s")

    def decode_step(p, c, b):
        return serve_decode(p, c, b, cfg)

    decode = jax.jit(decode_step)
    outs = [list(s) for s in ids]
    tok_ids = jnp.argmax(logits, axis=-1)[:, None]
    t0 = time.perf_counter()
    for _ in range(args.max_new):
        for i, t in enumerate(np.asarray(tok_ids)[:, 0]):
            outs[i].append(int(t))
        logits, cache = decode(params, cache, {"token": tok_ids})
        tok_ids = jnp.argmax(logits, axis=-1)[:, None]
    dt = time.perf_counter() - t0
    n_tok = args.max_new * len(args.prompts)
    print(f"decode: {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s, untrained weights)")
    for prompt, seq in zip(args.prompts, outs):
        text = tok.decode(np.asarray(seq))
        print(f"  {prompt!r} -> {text[:80]!r}")


if __name__ == "__main__":
    main()
