"""How far the ResNet's local training moves with the shape it is compiled at.

The training engines run the same per-client arithmetic as differently
shaped programs: the single-device scan vmaps the whole cohort of K
clients, each shard of the sharded engine vmaps K / shards of them, and
the host loops vmap whichever clients succeeded or completed. This probe
takes ``chip_smoke.py``'s training configuration at its first-round
parameters and, for default and highest matmul precision, reports:

  forward   the first layer whose activations differ between one forward
            pass over the cohort's first batch (K * batch_size rows) and
            the same rows in 4 slices (the four-chip sharded engine's)
  cohort    10 local SGD steps per client, whole cohort against the
            cohort in 4 slices: the largest relative difference
            of a client's mean loss, and of the cohort's train_loss
  one ulp   (highest only) the whole cohort from parameters moved by one
            ulp against the unmoved run: the same two numbers, i.e. how
            much 10 local steps amplify a last-bit difference
  rounds    (highest only) ``run_fl(engine="scanned")`` from the configured
            start against the same run from the start moved by one ulp:
            per round, how far a last-bit difference carries end to end

  python precision_probe.py           # K = 100 of 2,618 clients
  python precision_probe.py --small   # K = 8 of 64, 2 local steps, any host
"""
import argparse
import os
import sys

CHUNKS = 4
SMALL = dict(k=8, n_clients=64, local_steps=2)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


def forward_layers(cfg, p, x):
    """``resnet_forward`` with every layer's output kept, keyed in
    order (``"00 stem conv"``, ...)."""
    import jax
    from repro.models import resnet as r

    out = []
    h = r.conv2d(x, p["stem"])
    out.append(("stem conv", h))
    h = jax.nn.relu(r.group_norm(h, **p["stem_norm"]))
    for si, blocks in enumerate(p["stages"]):
        for bi, blk in enumerate(blocks):
            res, s = h, 2 if (bi == 0 and si > 0) else 1
            h2 = r.conv2d(h, blk["conv1"], stride=s)
            out.append((f"stage {si} block {bi} conv1", h2))
            h2 = jax.nn.relu(r.group_norm(h2, **blk["norm1"]))
            h2 = r.group_norm(r.conv2d(h2, blk["conv2"]), **blk["norm2"])
            out.append((f"stage {si} block {bi} conv2+norm", h2))
            if "proj" in blk:
                res = r.conv2d(res, blk["proj"], stride=s)
            h = jax.nn.relu(res + h2)
    out.append(("logits", r.resnet_forward(cfg, p, x)))
    return {f"{j:02d} {name}": a for j, (name, a) in enumerate(out)}


def max_rel(a, b):
    """Largest difference relative to the largest magnitude of ``a``."""
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(a)), 1e-30))


def probe(precision, cfg, params, x, y, keys, nudge):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.federated.server import _cohort_train_fn
    from repro.models import resnet

    resnet.F32 = precision          # read by conv2d and the head at trace
    name = "highest" if precision is not None else "default"
    k, chunks = x.shape[0], CHUNKS
    c = k // chunks

    rows = x[:, :cfg.batch_size].reshape((-1,) + x.shape[2:])
    fwd = jax.jit(lambda p, xb: forward_layers(cfg.model, p, xb))
    whole = fwd(params, rows)
    sliced = [fwd(params, rows[i * c * cfg.batch_size:
                               (i + 1) * c * cfg.batch_size])
              for i in range(chunks)]
    first = "none"
    for layer in sorted(whole):
        a = whole[layer]
        b = jnp.concatenate([s[layer] for s in sliced])
        if not bool(jnp.array_equal(a, b)):
            first = f"{layer} (max rel diff {max_rel(a, b):.3g})"
            break
    print(f"{name}: forward {k * cfg.batch_size} rows vs {chunks} slices: "
          f"first differing layer: {first}", flush=True)

    cohort = jax.jit(_cohort_train_fn(cfg.model, cfg.local_steps,
                                      cfg.batch_size, cfg.client_lr))
    _, _, loss = cohort(params, x, y, keys)
    loss_sl = jnp.concatenate([
        cohort(params, x[i * c:(i + 1) * c], y[i * c:(i + 1) * c],
               keys[i * c:(i + 1) * c])[2] for i in range(chunks)])
    print(f"{name}: cohort {k} vs {chunks} x {c}: client mean loss max rel "
          f"diff {max_rel(loss, loss_sl):.3g}; train_loss "
          f"{float(jnp.mean(loss)):.7f} vs {float(jnp.mean(loss_sl)):.7f}",
          flush=True)
    if nudge:
        up = jax.tree.map(lambda w: jnp.nextafter(w, jnp.inf), params)
        _, _, loss_up = cohort(up, x, y, keys)
        print(f"{name}: cohort {k}, parameters one ulp up: client mean loss "
              f"max rel diff {max_rel(loss, loss_up):.3g}; train_loss "
              f"{float(jnp.mean(loss)):.7f} vs "
              f"{float(jnp.mean(loss_up)):.7f}", flush=True)
    resnet.F32 = jax.lax.Precision.HIGHEST
    return np.asarray(loss)


def end_to_end(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.federated import run_fl, server

    base = run_fl(cfg, engine="scanned")
    init = server.init_resnet
    server.init_resnet = lambda *a: jax.tree.map(
        lambda w: jnp.nextafter(w, jnp.inf), init(*a))
    try:
        up = run_fl(cfg, engine="scanned")
    finally:
        server.init_resnet = init
    same = all(getattr(base, f) == getattr(up, f)
               for f in ("cum_dropouts", "participation"))
    print(f"highest: {cfg.rounds} rounds from a start one ulp up: "
          f"integer fields {'identical' if same else 'DIFFER'}", flush=True)
    for f in ("train_loss", "mean_battery", "test_acc"):
        d = np.abs(np.asarray(getattr(base, f), np.float64)
                   - np.asarray(getattr(up, f), np.float64))
        print(f"  {f} per-round |diff|: "
              + ", ".join(f"{x:.3g}" for x in d), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true",
                    help="a cut-down run for a CPU host: " + str(SMALL))
    args = ap.parse_args(argv)

    import jax
    from chip_smoke import train_config
    from repro.core import SelectorConfig
    from repro.federated.server import _fused_setup

    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"jax={jax.__version__}", flush=True)
    cfg = train_config()
    if args.small:
        cfg = train_config(selector=SelectorConfig(kind="eafl", k=SMALL["k"]),
                           n_clients=SMALL["n_clients"],
                           local_steps=SMALL["local_steps"])
    _, data, _, params, *_ = _fused_setup(cfg)
    k = cfg.selector.k
    x, y = data["x"][:k], data["y"][:k]
    keys = jax.random.split(jax.random.PRNGKey(1), k)
    default = probe(None, cfg, params, x, y, keys, False)
    highest = probe(jax.lax.Precision.HIGHEST, cfg, params, x, y, keys, True)
    print(f"default vs highest: client mean loss max rel diff "
          f"{max_rel(highest, default):.3g}", flush=True)
    end_to_end(cfg)
    return 0


if __name__ == "__main__":
    sys.exit(main())
