"""The benchmark of horovod_tpu: harness, yardsticks, plain references and the
data files of every configuration, cell and metric (see ``run.py``)."""
