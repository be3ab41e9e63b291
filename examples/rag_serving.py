"""End-to-end spatio-temporal RAG (the paper's application): geo-tagged
document store -> CubeGraph filtered retrieval -> LM generation.

    PYTHONPATH=src python examples/rag_serving.py
"""
import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.core import CubeGraphConfig
from repro.core.filters import BoxFilter
from repro.core.workloads import make_dataset
from repro.models import build_model, init_params
from repro.serving.rag import Document, DocumentStore, RAGPipeline

enable_compile_cache()

# Corpus: 2000 geo-tagged "reports" (embedding + (lon, lat, t) + token span)
x, s = make_dataset(2000, 32, 3, seed=0)
rng = np.random.default_rng(1)
docs = [Document(doc_id=i, tokens=rng.integers(2, 250, 16).astype(np.int32),
                 embedding=x[i], metadata=s[i]) for i in range(2000)]
store = DocumentStore(docs, CubeGraphConfig(n_layers=3))

# Generator backbone: any assigned arch (reduced config on CPU).
cfg = get_config("gemma3-1b", smoke=True)
model = build_model(cfg)
params = init_params(model.param_specs(), jax.random.key(0))
pipe = RAGPipeline(store, model, params, max_context=96)

# "flooded streets in this district during the last week"
district = BoxFilter(lo=np.asarray([0.1, 0.2, 0.6], np.float32),
                     hi=np.asarray([0.4, 0.5, 0.9], np.float32))
query_tokens = rng.integers(2, 250, 8).astype(np.int32)
answer, retrieved = pipe.answer(query_tokens, district, k=4, max_new=12)

print(f"retrieved {len(retrieved)} docs inside the district filter:")
for d in retrieved:
    print(f"  doc {d.doc_id}: meta={np.round(d.metadata, 3)}")
print("generated token ids:", answer[-12:])

# Streaming ingestion (paper §4.4): insert fresh reports, query again.
fresh = [Document(doc_id=2000 + i,
                  tokens=rng.integers(2, 250, 16).astype(np.int32),
                  embedding=x[i] + 0.01, metadata=np.asarray([0.25, 0.35, 0.7]))
         for i in range(16)]
store.insert(fresh)
answer2, retrieved2 = pipe.answer(query_tokens, district, k=4, max_new=12)
print("after insert, retrieved ids:", [d.doc_id for d in retrieved2])
