"""``parallel.runner.run_distributed`` over the configuration's chips:
the staged SPMD runner, with ``all_to_all`` between shards."""


def run(sess, df, config):
    from spark_rapids_tpu.parallel.runner import run_distributed

    return run_distributed(sess, df, n_devices=config["chips"]).to_rows()


def faults(metrics, config):
    out = []
    if metrics.get("fault.degradeLevel") != 0:
        out.append(f"fault.degradeLevel is "
                   f"{metrics.get('fault.degradeLevel')}")
    if metrics.get("distributed.numShardDevices") != config["chips"]:
        out.append(f"leaf shards on "
                   f"{metrics.get('distributed.numShardDevices')} devices, "
                   f"not {config['chips']}")
    # dispatch wall of exchange-bearing programs, compiles inside: only
    # ever a sign that one ran, never a time
    if not metrics.get("shuffle.collectiveTimeNs", 0) > 0:
        out.append("no exchange-bearing mesh program ran")
    return out
