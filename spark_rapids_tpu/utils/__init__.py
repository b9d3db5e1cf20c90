from spark_rapids_tpu.utils.arm import closing_on_except, close_all, Retainable
from spark_rapids_tpu.utils.metrics import Metric, MetricSet
