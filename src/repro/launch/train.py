"""Training driver — a thin flags → RunSpec → Session shim.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 200 --clients 8 --method ef21_sgdm --compressor block_topk

All assembly (mesh, ShardPlan, EFConfig, data pipeline, jitted step,
checkpointing) lives in launch/session.py behind the RunSpec
(launch/spec.py); this module only parses flags and narrates. ``--spec
FILE`` loads a serialized RunSpec instead of (or as a base for) flags.

``--resume`` restores the FULL training state (params + opt_state + ef_state
+ data cursor) from the latest checkpoint under --ckpt-dir; the RunSpec
embedded in the checkpoint is the source of truth when no spec flags are
passed, and a mismatching flag-built spec is refused unless
--allow-spec-mismatch (DESIGN.md §7).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

from repro.launch import spec as spec_lib


def main(argv=None) -> None:
    ap = argparse.ArgumentParser("repro.launch.train")
    spec_lib.add_flags(ap)
    ap.add_argument("--steps", type=int, default=200,
                    help="train until this ABSOLUTE step count")
    ap.add_argument("--resume", action="store_true",
                    help="restore full state from the latest ckpt in "
                         "--ckpt-dir (spec embedded there wins unless other "
                         "spec flags are passed)")
    ap.add_argument("--allow-spec-mismatch", action="store_true",
                    help="resume even when the flag-built spec differs from "
                         "the checkpoint's")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--publish-stream", default=None, metavar="DIR",
                    help="publish every downlink wire record to this stream "
                         "dir (core/stream.py) so serving replicas can "
                         "subscribe (launch/fleet.py)")
    ap.add_argument("--bootstrap-every", type=int, default=0,
                    help="with --publish-stream: also write a bootstrap "
                         "checkpoint into the stream every N steps (0 = "
                         "only the initial one)")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="join a multi-process jax.distributed fleet at this "
                         "coordinator (process 0 hosts it) before any jax "
                         "device access; needs --num-processes/--process-id "
                         "(launch/multiproc.py)")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args(argv)
    spec = spec_lib.RunSpec.from_args(args)

    if args.coordinator is not None:
        if args.num_processes is None or args.process_id is None:
            ap.error("--coordinator needs --num-processes and --process-id")
        # BEFORE the session import chain touches jax device state
        from repro.launch import multiproc
        multiproc.distributed_init(args.coordinator, args.num_processes,
                                   args.process_id)

    from repro.launch import compile_cache
    from repro.launch.session import Session  # defer the jax-heavy import

    compile_cache.enable()
    if args.resume:
        if not spec.ckpt_dir:
            ap.error("--resume needs --ckpt-dir")
        # bare `--ckpt-dir D --resume` reconstructs the run purely from the
        # checkpoint's embedded RunSpec; any EXPLICITLY passed spec flag
        # (even one equal to a default — flags parse as None when unset)
        # enforces the flag-built spec against the checkpoint's hash
        explicit = spec_lib.explicit_fields(
            args, ignore=("ckpt_dir", "ckpt_every"))
        try:
            if args.spec_file:
                # an explicit spec FILE is the full experiment definition
                sess = Session.resume(
                    spec.ckpt_dir, spec=spec,
                    allow_spec_mismatch=args.allow_spec_mismatch)
            else:
                # explicit flags layer ONTO the checkpoint's embedded spec
                # ('--resume --eta 0.2' = same run, new eta — never
                # 'defaults plus eta')
                overrides = {f: getattr(args, f) for f in explicit}
                sess = Session.resume(
                    spec.ckpt_dir, overrides=overrides or None,
                    allow_spec_mismatch=args.allow_spec_mismatch)
            print(f"resumed {sess.spec.arch} from {spec.ckpt_dir} "
                  f"@ step {sess.step}")
        except FileNotFoundError:
            # idempotent-restart idiom: wrappers always pass --resume; an
            # empty/absent ckpt dir means first launch → start from scratch
            print(f"no checkpoint under {spec.ckpt_dir}; starting fresh")
            sess = Session(spec)
        # checkpoint POLICY is runtime-owned (excluded from spec_hash): an
        # explicit --ckpt-every on the resume command line applies even when
        # the embedded spec wins everything else
        if args.ckpt_every is not None:
            sess.spec = dataclasses.replace(sess.spec,
                                            ckpt_every=args.ckpt_every)
    else:
        sess = Session(spec)

    # printed from the spec the session ACTUALLY runs (a bare --resume
    # adopts the checkpoint's embedded spec, not the flag defaults)
    table = sess.schedule_table()
    if table is not None:
        # per-group schedule: the RESOLVED group table (leaf/param counts,
        # per-group plan + degradation reasons, wire words) IS the plan line
        print("compression schedule (first-match-wins):")
        print(table)
    else:
        plan, reason = sess.spec.plan()
        print(f"carrier={sess.spec.carrier} plan={plan}"
              + (f" (degraded: {reason})" if reason else ""))
        if sess.spec.downlink_carrier != "dense":
            dplan, dreason = sess.spec.downlink_plan()
            print(f"downlink={sess.spec.downlink_carrier} plan={dplan}"
                  + (f" (degraded: {dreason})" if dreason else ""))
    pp = spec_lib.participation_preview(sess.spec)
    if pp["mode"] != "full":
        print(f"participation mode={pp['mode']} fraction={pp['fraction']} "
              f"seed={pp['seed']} cohort={pp['cohort']}/{pp['n']} per round")
    hp = spec_lib.hops_preview(sess.spec)
    if hp["hierarchical"]:
        print(f"hops pods={hp['pods']} cross={hp['cross_carrier']}"
              f":{hp['cross_ratio']} "
              f"clients_per_pod={hp['clients_per_pod']}"
              + (" (trivial cross: flat-equivalent)"
                 if hp["trivial_cross"] else ""))

    if args.publish_stream:
        sess.publish_to(args.publish_stream,
                        bootstrap_every=args.bootstrap_every)
        print(f"publishing wire records to {args.publish_stream}")

    sess.train(args.steps, log_every=args.log_every, verbose=True)
    if sess.spec.ckpt_dir:
        print(f"saved checkpoint @ {sess.step}")
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(sess.history, f, indent=1)


if __name__ == "__main__":
    main()
