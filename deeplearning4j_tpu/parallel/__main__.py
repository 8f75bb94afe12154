"""Parallel-training CLI — `python -m deeplearning4j_tpu.parallel
--model model.zip --data train.csv --label-index -1 --num-classes 3`.

Reference analog: `ParallelWrapperMain.java`
(`deeplearning4j-scaleout-parallelwrapper/.../parallelism/main/`,
SURVEY.md §2.10): load a serialized model, train it data-parallel over the
local devices, save it back.
"""
import argparse


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="deeplearning4j_tpu.parallel",
        description="Train a serialized model data-parallel over the "
                    "local device mesh")
    ap.add_argument("--model", required=True, help="model zip "
                    "(ModelSerializer format)")
    ap.add_argument("--data", required=True, help="numeric CSV")
    ap.add_argument("--label-index", type=int, default=-1)
    ap.add_argument("--num-classes", type=int, default=0,
                    help="one-hot classes; 0 = regression")
    ap.add_argument("--batch-size", type=int, default=64,
                    help="global batch size")
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--workers", type=int, default=0,
                    help="devices on the data axis (0 = all)")
    ap.add_argument("--averaging-frequency", type=int, default=0,
                    help="0 = per-step sync allreduce; N = local SGD with "
                         "parameter averaging every N steps")
    ap.add_argument("--save-to", default=None,
                    help="output model zip (default: overwrite --model)")
    args = ap.parse_args(argv)

    import jax

    from ..datasets.records import RecordReaderDataSetIterator
    from ..util.platform import enable_compilation_cache
    from ..util.serializer import ModelSerializer
    from . import ParallelTrainer, TrainingMode, make_mesh

    enable_compilation_cache()
    net = ModelSerializer.restore(args.model)
    it = RecordReaderDataSetIterator(
        args.data, batch_size=args.batch_size,
        label_index=args.label_index, num_classes=args.num_classes,
        regression=args.num_classes <= 0)
    n = args.workers or len(jax.devices())
    trainer = ParallelTrainer(
        net, mesh=make_mesh({"data": n}),
        mode=(TrainingMode.AVERAGING if args.averaging_frequency
              else TrainingMode.SYNC),
        averaging_frequency=args.averaging_frequency or 1)
    for _ in range(args.epochs):
        it.reset()
        while it.has_next():
            trainer.fit(it.next())
    ModelSerializer.write_model(net, args.save_to or args.model)
    print(f"trained {args.epochs} epoch(s) on {n} device(s); "
          f"final score {float(trainer.score()):.6f}")


if __name__ == "__main__":
    main()
