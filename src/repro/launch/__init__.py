# Launch layer: train.py / serve.py (CLIs), steps.py (the step, pipeline,
# controller, serving-front and supervisor builders the benchmark and the
# CLIs call), hlo_analysis.py (roofline accounting), compile_cache.py
# (persistent compile cache).
