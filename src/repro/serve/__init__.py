from repro.core.kvwire import (quantize_kv, dequantize_kv, make_quant_kv,
                               update_quant_kv, is_quant_kv, kv_bits_of,
                               make_paged_kv, gather_pages, scatter_token,
                               scatter_tokens, scatter_prefill,
                               permute_pages, reset_table_rows,
                               quantize_state, dequantize_state,
                               is_quant_state, cache_nbytes)
from .engine import (Engine, EngineConfig, PagedConfig, PagedEngine,
                     greedy_sample, temperature_sample)
from .pool import PagedKVPool, make_pool_pages, pool_nbytes
from .scheduler import Completion, Request, Scheduler
from .server import RequestParams, Server
