SELECT ss_customer_sk, ss_store_sk, COUNT(*) AS cnt,
       SUM(ss_quantity) AS qty, SUM(ss_net_paid) AS paid
FROM store_sales GROUP BY ss_customer_sk, ss_store_sk
ORDER BY paid DESC, ss_customer_sk, ss_store_sk
LIMIT 100
