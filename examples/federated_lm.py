"""Beyond-paper example: the recruitment technique is model-agnostic.

Federated fine-tuning of a *reduced* smollm-135m across synthetic hospital
text shards: each client's disclosure is a TOKEN histogram (10 vocabulary
buckets) + sample size — exactly the paper's (P_co, n_c) tuple, applied to a
language model instead of the LoS GRU.  Recruitment then gates which
hospitals join the federation, and FedAvg aggregates transformer weights.

    PYTHONPATH=src python examples/federated_lm.py
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.histogram import token_histogram
from repro.core.recruitment import BALANCED, ClientStats, recruit
from repro.federated.fedavg import aggregate
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models.zoo import Model
from repro.optim.adamw import AdamW

NUM_CLIENTS = 12
SEQ, BATCH = 64, 4
ROUNDS, LOCAL_STEPS = 3, 5


def make_client_corpus(rng, vocab, skew: float):
    """Non-IID token distributions: each hospital's notes favor a band of the
    vocabulary (specialty jargon); skew controls divergence."""
    center = rng.uniform(0, vocab)
    width = vocab * (1.0 - 0.8 * skew)
    n_samples = int(rng.integers(40, 400))
    toks = (rng.normal(center, width, size=(n_samples, SEQ + 1)) % vocab).astype(np.int32)
    return toks


def main() -> None:
    enable_compile_cache()
    cfg = get_config("smollm-135m").reduced()
    model = Model(cfg, remat=False)
    optimizer = AdamW(learning_rate=1e-3)
    rng = np.random.default_rng(0)

    corpora = [make_client_corpus(rng, cfg.vocab_size, skew=rng.uniform(0, 1)) for _ in range(NUM_CLIENTS)]

    # recruitment on token histograms — the paper's disclosure, LM flavor
    stats = [
        ClientStats(client_id=i, counts=token_histogram(c[:, 1:], cfg.vocab_size), n=len(c))
        for i, c in enumerate(corpora)
    ]
    res = recruit(stats, dataclasses.replace(BALANCED, gamma_th=0.3))
    print(f"recruited {res.num_recruited}/{NUM_CLIENTS} hospital text shards: "
          f"{sorted(res.recruited_ids.tolist())}")

    params = model.init(jax.random.key(0))
    step = jax.jit(make_train_step(model, optimizer))

    for rnd in range(ROUNDS):
        client_params, weights = [], []
        for cid in res.recruited_ids:
            corpus = corpora[int(cid)]
            p, opt_state = params, optimizer.init(params)
            losses = []
            for k in range(LOCAL_STEPS):
                idx = rng.integers(0, len(corpus), BATCH)
                toks = corpus[idx]
                batch = {
                    "tokens": jnp.asarray(toks[:, :-1]),
                    "labels": jnp.asarray(toks[:, 1:]),
                }
                p, opt_state, metrics = step(p, opt_state, batch)
                losses.append(float(metrics["loss"]))
            client_params.append(p)
            weights.append(len(corpus))
        params = aggregate(client_params, weights)
        print(f"round {rnd}: mean local loss {np.mean(losses):.4f} "
              f"({len(client_params)} clients aggregated)")

    print("federated LM fine-tuning done — recruitment + FedAvg over a transformer.")


if __name__ == "__main__":
    main()
