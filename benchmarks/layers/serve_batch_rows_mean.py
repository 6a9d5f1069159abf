"""Layer: serving_queue. Mean rows of the batch a request rode in
(``batch_rows`` of the request's meta), over requests."""


def read(facts):
    values = facts.get("batch_rows")
    return sum(values) / len(values) if values else None
