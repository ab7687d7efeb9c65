"""Offline multi-model scaling runs to convergence (VERDICT r3 item 8).

Round 3 proved the approach-1 fit MECHANICS on 600-step smoke curves over the
template corpus; that corpus is memorizable (the 256ch model reached val 0.16),
so converged curves there carry no scaling physics. This driver:

1. generates a deterministic HIGH-ENTROPY corpus (seeded order-1 Markov chain
   over a zipfian word vocabulary — enough entropy that the model grid stays
   capacity-limited, with a nonzero irreducible loss),
2. trains the three study model sizes to convergence (val_loss plateau) for
   each requested seed via the real CLM CLI on ``TextFileDataModule``,
3. exports curves to ``examples/scaling/clm/data/offline_runs/seed<k>/`` and
   runs the free-exponent approach-1 fit per seed
   (``scaling_study.py fit-demo --free-exponents``), reporting exponent
   stability across seeds.

    python tools/scaling_runs.py [--seeds 0 1] [--steps 2000] [--platform cpu]
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "examples", "scaling", "clm", "data", "offline_runs")

# (num_channels, num_self_attention_layers) — total layers incl. the hybrid
# cross-attention layer is sa+1, matching the study grid labels 3l/4l/5l
GRID = [(128, 2), (192, 3), (256, 4)]


def make_corpus(path: str, n_words: int = 2_000_000, vocab: int = 2048, seed: int = 7) -> None:
    """Seeded order-1 Markov word stream (state = previous word) over a zipfian vocabulary.

    Entropy is controlled by the per-state successor fan-out (8): an ideal
    model's loss floor is ~log(8)/avg_word_len nats/byte > 0, and word
    statistics give mid-sized models something real to learn — unlike the
    template corpus, bigger models cannot simply memorize their way to ~0.
    """
    rng = np.random.default_rng(seed)
    words = np.array([f"w{i}" for i in range(vocab)])
    # zipfian unigram draw for successor tables: common words are common
    ranks = np.arange(1, vocab + 1)
    p = 1.0 / ranks
    p /= p.sum()
    fanout = 8
    succ = rng.choice(vocab, size=(vocab, fanout), p=p)
    state = 0
    out = []
    for _ in range(n_words):
        state = int(succ[state, rng.integers(fanout)])
        out.append(words[state])
    text = " ".join(out)
    with open(path, "w") as f:
        f.write(text)


def corpus_valid(path: str, min_bytes: float = 30e6) -> bool:
    """True iff ``path`` is a complete seed-7 ``make_corpus`` stream: size
    plus the chain's deterministic first words. /tmp is world-shared — a
    foreign or truncated file would silently detach a run from the corpus's
    analytic entropy floor. Shared by flagship_convergence and the int8
    trained probe so the guard and the generator stay in one file."""
    try:
        if os.path.getsize(path) < min_bytes:
            return False
        with open(path) as f:
            return f.read(16).startswith("w725 w3 w1037 ")
    except OSError:
        return False


def run_one(channels: int, sa_layers: int, seed: int, steps: int, corpus: str,
            out_csv: str, platform: str) -> None:
    root = tempfile.mkdtemp(prefix=f"scaling_{channels}ch_s{seed}_")
    # platform "default" leaves backend selection to JAX (i.e. the real
    # accelerator when one is attached); a named platform pins it
    select = "" if platform in ("", "default") else (
        f"import jax; jax.config.update('jax_platforms', '{platform}')\n"
    )
    code = (
        select
        + f"import sys; sys.path.insert(0, {REPO!r})\n"
        "from perceiver_io_tpu.scripts.text.clm import main\n"
        f"main({_argv(channels, sa_layers, seed, steps, corpus, root)!r})\n"
    )
    env = dict(os.environ)
    env.setdefault("XLA_FLAGS", "")
    t = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True)
    if t.returncode != 0:
        raise RuntimeError(f"run {channels}ch seed {seed} failed:\n{t.stderr[-3000:]}")
    src = os.path.join(root, "logs", "run", "metrics.csv")
    os.makedirs(os.path.dirname(out_csv), exist_ok=True)
    shutil.copy(src, out_csv)
    shutil.rmtree(root, ignore_errors=True)


def _argv(channels, sa_layers, seed, steps, corpus, root):
    return [
        "fit",
        "--data.dataset=textfile",
        f"--data.train_file={corpus}",
        "--data.max_seq_len=1024",
        "--data.batch_size=8",
        f"--data.cache_dir={root}/cache",
        "--model.max_latents=256",
        f"--model.num_channels={channels}",
        f"--model.num_self_attention_layers={sa_layers}",
        "--model.num_heads=8",
        f"--trainer.max_steps={steps}",
        "--trainer.val_interval=200",
        "--trainer.log_interval=100",
        "--trainer.devices=1",
        "--trainer.checkpoint=false",
        f"--trainer.seed={seed}",
        f"--trainer.default_root_dir={root}/logs",
        "--trainer.name=run",
        "--optimizer.lr=6e-4",
        "--optimizer.warmup_steps=100",
    ]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", type=int, nargs="*", default=[0, 1])
    p.add_argument("--steps", type=int, default=2000)
    p.add_argument("--platform", default="cpu")
    p.add_argument("--corpus", default=None, help="existing corpus file (default: generate)")
    p.add_argument("--jobs", type=int, default=3, help="parallel runs")
    args = p.parse_args(argv)

    corpus = args.corpus
    if corpus is None:
        corpus = os.path.join(tempfile.gettempdir(), "scaling_corpus_markov1.txt")
        if not os.path.exists(corpus):
            print("generating corpus ...", flush=True)
            make_corpus(corpus)
    print(f"corpus: {corpus} ({os.path.getsize(corpus)/1e6:.1f} MB)")

    from concurrent.futures import ThreadPoolExecutor

    jobs = []
    with ThreadPoolExecutor(max_workers=args.jobs) as ex:
        for seed in args.seeds:
            for channels, sa in GRID:
                out_csv = os.path.join(OUT, f"seed{seed}", f"clm_{channels}ch_{sa + 1}l.csv")
                jobs.append(
                    (out_csv,
                     ex.submit(run_one, channels, sa, seed, args.steps, corpus, out_csv,
                               args.platform))
                )
        for out_csv, fut in jobs:
            fut.result()
            print(f"done: {out_csv}", flush=True)

    print("\nper-seed free-exponent fits:")
    for seed in args.seeds:
        runspecs = []
        for c, l in GRID:
            runspecs += [
                "--run",
                os.path.join(OUT, f"seed{seed}", f"clm_{c}ch_{l + 1}l.csv") + f":{c}:{l + 1}",
            ]
        # no PYTHONPATH override: the package import works installed or via cwd
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "examples", "scaling", "scaling_study.py"),
             "fit-demo", "--free-exponents", *runspecs],
            capture_output=True, text=True, cwd=REPO,
        )
        if r.returncode != 0:
            raise RuntimeError(f"fit for seed {seed} failed:\n{r.stderr[-2000:]}")
        print(f"--- seed {seed} ---")
        print(r.stdout)


if __name__ == "__main__":
    main()
